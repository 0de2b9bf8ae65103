"""Slot-multiplexed micro-batching for stateful SNN streams.

``StreamScheduler`` generalizes the continuous batcher's fixed slot grid
(``launch.batching.SlotGrid``) from token decode to SNN timesteps. One
jitted chunk step with static shapes — events ``[chunk_len, n_slots,
n_in]``, valid ``[chunk_len, n_slots]`` — advances every active stream by
up to ``chunk_len`` timesteps; admitted streams claim a lane (reset in
place), retired streams free it. Idle or ragged tails are masked invalid,
so they neither perturb state nor accrue telemetry: an empty slot costs
exactly zero counted events.

Each grid step runs through three explicit phases (see serving/staging.py):

1. **stage** — advance the virtual clock, drain newly arrived chunks into
   session buffers (from the async ingest queues, or by polling sources
   inline), admit queued sessions into free lanes, pack up to
   ``chunk_len`` buffered timesteps per active slot, and mark sessions
   that exhaust after this step;
2. **dispatch** — enqueue the single compiled chunk fn on the staged
   buffers (asynchronous — the host does not wait) and free the lanes of
   marked sessions so the next stage phase can re-admit into them;
3. **retire** — fetch the step's metrics (the only device wait), route
   window-end logits back to sessions as predictions, fold per-lane
   metrics into per-stream telemetry, finalize retiring sessions, and
   feed/drive the topology service.

With ``pipeline_depth=0`` (default) the phases run back-to-back — the
serial reference behavior. With ``pipeline_depth=1`` the scheduler
double-buffers: step ``t+1`` is staged while the device computes step
``t``, hiding host event assembly behind compute; lane surgery and
telemetry reads no longer force a device sync per step. Both modes
produce bit-identical per-stream trajectories (pinned in
``tests/test_serving_pipeline.py``) — call :meth:`flush` (or use
:meth:`run_until_drained`, which does) to drain in-flight bookkeeping.

**QoS tiers.** Passing ``tiers=[TierConfig(...), ...]`` splits the fleet
into per-tier slot grids — an ``interactive`` tier with a small
``chunk_len`` (windows close, and predictions land, after fewer staged
timesteps) next to a ``bulk`` tier with a long one (fewer dispatches per
timestep) — each tier owning its own grid, lane-batched device state and
jitted chunk fn over the *same* shared exec params. Tier assignment
happens at admission (``submit(session, tier=...)`` or
``session.tier``); per-tier wall/energy rollups land under a ``tier``
label in telemetry. Every tier's chunk fn compiles once at warmup and
never again (``n_compiles`` stays 1). Single-tier construction (the
default) is exactly the old scheduler: one tier named "default" built
from ``n_slots``/``chunk_len``.

**Async ingestion.** With ``ingest=True`` (or an ``IngestConfig`` /
``IngestWorker``), source polling moves off the grid-step critical path
to a dedicated worker thread (serving/ingest.py); ``_poll_sources``
becomes a lock-protected queue drain. Bit-identical to inline polling by
construction — the worker replays the virtual clock exactly. Call
:meth:`close` when done to stop the thread.

**Adaptive pipelining.** With ``autopilot=True`` (or an
``AutopilotConfig`` / ``DepthAutopilot``), a host-side controller
(serving/autopilot.py) retunes ``pipeline_depth`` from the EMA of the
measured per-step host/device overlap ratio — host-bound fleets deepen,
device-bound fleets hold — with hysteresis and a bounded range. Depth
changes land only at drain-safe boundaries (flush, then resize the empty
pipelines), so adaptive trajectories stay bit-identical to every fixed
depth they visited.

With a ``("slots",)`` mesh (``launch.mesh.make_serving_mesh``) the grid
shards over devices: each tier's slot allocation pads to the device
count (``launch.sharding.tier_slot_allocation``), the chunk step runs
under slot-axis ``shard_map`` (bit-identical to 1-device — see
serving/adapt.py), and lane surgery re-places its result so the slot
sharding survives admit/retire.

With a ``TopologyService`` attached (single-tier fleets only — an epoch
folds the whole fleet's deltas into one base), the chunk fn is built
with ``want_factors=True``: every retire phase feeds the service's DSST
accumulators and ``maybe_evolve_topology()`` runs due prune/regrow
epochs *between* grid steps; the evolved ``(params, deltas)`` keep their
shapes and slot shardings, so the swap is atomic from the streams' point
of view and the chunk step never recompiles.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import engine
from repro.core.snn import (SNNConfig, init_stream_deltas, init_stream_state,
                            serving_params)
from repro.launch import sharding
from repro.launch.batching import SlotGrid
from repro.obs.trace import NULL_TRACER, Tracer

from .adapt import AdaptConfig, make_chunk_fn
from .autopilot import AutopilotConfig, DepthAutopilot
from .ingest import IngestConfig, IngestWorker
from .session import (SessionStatus, StreamSession, WindowPrediction,
                      reset_lane)
from .staging import InFlight, LaneRecord, StagedChunk, StagingPipeline
from .telemetry import FleetTelemetry


@dataclasses.dataclass(frozen=True)
class TierConfig:
    """One QoS tier's grid geometry.

    ``chunk_len`` is the latency/throughput knob: a small chunk means
    window-end predictions surface after fewer staged timesteps
    (interactive), a large one amortizes dispatch overhead over more
    timesteps per step (bulk).  ``n_slots`` is the tier's lane count
    (rounded up per device under a mesh).
    """
    name: str
    chunk_len: int
    n_slots: int

    def __post_init__(self):
        if not self.name:
            raise ValueError("tier name must be non-empty")
        if self.chunk_len < 1 or self.n_slots < 1:
            raise ValueError(
                f"tier {self.name!r} needs chunk_len >= 1 and n_slots >= 1, "
                f"got {self.chunk_len}/{self.n_slots}")


class _Tier:
    """Runtime state of one tier: its slot grid, lane-batched device
    state/deltas (+ shardings), compiled chunk fn, and staging pipeline.
    ``slot0`` is the tier's offset in the fleet-global slot numbering
    (``step()`` returns global slot ids; everything internal is local)."""

    __slots__ = ("name", "chunk_len", "n_slots", "slot0", "grid", "state",
                 "deltas", "chunk_fn", "pipeline", "state_sh")

    def __init__(self, name: str, chunk_len: int, n_slots: int, slot0: int):
        self.name, self.chunk_len = name, chunk_len
        self.n_slots, self.slot0 = n_slots, slot0
        self.state_sh = None


class StreamScheduler:
    """Drives a fleet of :class:`StreamSession`\\ s over per-tier slot grids.

    Args:
      params:   frozen shared base params (stacked layout, ``core.snn``).
      cfg:      the fleet's :class:`SNNConfig`.
      n_slots:  grid width of the default tier (ignored when ``tiers`` is
        given; rounded up / floored per device with ``mesh``).
      chunk_len: timesteps per grid step of the default tier (static
        chunk-fn shape).
      adapt:    per-stream delta hygiene (:class:`AdaptConfig`).
      clock_dt_s: virtual seconds per grid step (drives source arrivals).
      telemetry: a :class:`FleetTelemetry` to fill (fresh one by default).
      mesh:     optional 1-D ``("slots",)`` mesh — shard every tier's grid.
      topology: optional :class:`TopologyService` — live DSST epochs
        (single-tier fleets only).
      pipeline_depth: 0 = serial phases (reference), 1 = double-buffered
        staging (overlap host packing with device compute), >1 = deeper
        queue (clamped to 1 while a live topology service is attached, so
        epochs land between the same grid steps as the serial path).
      want_factors: override the chunk fn's static DSST-factor mode; by
        default inferred — True iff a non-frozen topology service is
        attached. Note the mode is baked at compile time: a service that
        *becomes* frozen later stops paying the host transfer but keeps
        the (tiny) in-scan accumulators until the scheduler is rebuilt.
      compact: delta/weight layout of the hot path. ``None`` (default)
        auto-selects the compact N:M layout whenever the layer geometry is
        uniform: per-stream deltas are stored ``[S, L, J, T, bk, bo]``
        (memory scales with density, not ``K·N``) and the chunk step
        consumes the mask-free ``{"wc", "idx", "readout"}`` weight rep —
        no dense mask or dense ``[S, L, K, N]`` leaf exists in the serving
        jaxpr. ``False`` forces the dense baseline layout (the A/B
        reference). ``self.params`` stays the canonical dense layout
        either way; the compact exec rep is re-derived on the host at
        construction and after every topology swap.
      tracer: an ``obs.trace.Tracer`` recording phase-level spans
        (``sched.step/stage/poll_sources/admit/dispatch/retire/
        device_wait``, ``topology.epoch``, ``autopilot.decision/apply``);
        the shared no-op ``NULL_TRACER`` by default. Spans wrap host
        phases at already-synchronous points only — tracing on vs. off is
        bit-identical and leaves the serving jaxpr unchanged (pinned in
        ``tests/test_obs_serving.py``).
      tiers: optional QoS tier geometries (:class:`TierConfig` list,
        unique names). ``None`` = one tier named "default" built from
        ``n_slots``/``chunk_len`` — the exact pre-tier scheduler.
      ingest: async source ingestion — ``True`` (defaults), an
        :class:`IngestConfig`, or a prebuilt :class:`IngestWorker`.
        ``None``/``False`` polls sources inline in stage (the serial
        reference; bit-identical either way).
      autopilot: adaptive pipeline depth — ``True`` (defaults), an
        :class:`AutopilotConfig`, or a prebuilt :class:`DepthAutopilot`.
        ``None``/``False`` keeps ``pipeline_depth`` fixed. With a live
        topology service the controller's range is clamped to depth <= 1.
    """

    def __init__(self, params, cfg: SNNConfig, n_slots: int,
                 chunk_len: int = 8, adapt: Optional[AdaptConfig] = None,
                 clock_dt_s: float = 0.002,
                 telemetry: Optional[FleetTelemetry] = None,
                 mesh=None, topology=None, pipeline_depth: int = 0,
                 want_factors: Optional[bool] = None,
                 compact: Optional[bool] = None,
                 tracer: Optional[Tracer] = None,
                 tiers: Optional[Sequence[TierConfig]] = None,
                 ingest=None, autopilot=None):
        self.params, self.cfg = params, cfg
        if compact is None:
            compact = engine.geometry(cfg).uniform
        self.compact = compact
        self.mesh = mesh
        self.topology = topology          # Optional[TopologyService]
        if topology is not None and topology.cfg != cfg:
            # fail here, not at the first epoch with a half-evolved fleet
            raise ValueError("topology service was built for a different "
                             "SNNConfig than this scheduler's")
        live_topology = topology is not None and not topology.frozen
        if want_factors is None:
            want_factors = live_topology
        if live_topology and not want_factors:
            raise ValueError(
                "a live topology service consumes the chunk step's DSST "
                "factors; want_factors=False would starve it — drop the "
                "service or keep factors on")
        self.want_factors = want_factors
        if topology is not None:
            # an epoch due after step t must land before step t+1 is
            # dispatched; depth 1 preserves that, deeper queues would not
            pipeline_depth = min(pipeline_depth, 1)

        # -- tier geometry ----------------------------------------------------
        if tiers is None:
            tier_cfgs = [TierConfig("default", chunk_len=chunk_len,
                                    n_slots=n_slots)]
        else:
            tier_cfgs = list(tiers)
            if not tier_cfgs:
                raise ValueError("tiers must be a non-empty TierConfig list")
            names = [t.name for t in tier_cfgs]
            if len(set(names)) != len(names):
                raise ValueError(f"duplicate tier names in {names}")
            if topology is not None and len(tier_cfgs) > 1:
                raise ValueError(
                    "a topology service folds one fleet-wide delta grid "
                    "into the shared base; attach it to a single-tier "
                    "scheduler")
        if mesh is not None:
            # device-count-aware slot allocation, per tier: padded to a
            # multiple of the slot-mesh size so every device owns an equal
            # slot shard (padding lanes just idle — an empty slot is free),
            # and floored at 2 slots per device: at a local batch of 1
            # XLA:CPU drops the slot matmuls to a gemv with a different
            # K-reduction order, costing bit-identity with 1-device
            widths = sharding.tier_slot_allocation(
                [t.n_slots for t in tier_cfgs], mesh)
            tier_cfgs = [dataclasses.replace(t, n_slots=w)
                         for t, w in zip(tier_cfgs, widths)]

        self._tiers: List[_Tier] = []
        slot0 = 0
        for tc in tier_cfgs:
            tier = _Tier(tc.name, tc.chunk_len, tc.n_slots, slot0)
            slot0 += tc.n_slots
            tier.grid = SlotGrid(tc.n_slots)
            tier.state = init_stream_state(cfg, tc.n_slots)
            tier.deltas = init_stream_deltas(cfg, tc.n_slots, compact=compact)
            if mesh is not None:
                tier.state_sh = sharding.stream_shardings(tier.state, mesh)
                tier.state = jax.device_put(tier.state, tier.state_sh)
                tier.deltas = jax.device_put(tier.deltas,
                                             sharding.slot_sharding(mesh))
            # one compiled chunk fn per tier (its own [C, S] static shape
            # and its own trace counter); all tiers share cfg/adapt/exec rep
            tier.chunk_fn = make_chunk_fn(cfg, adapt, mesh=mesh,
                                          want_factors=want_factors)
            tier.pipeline = StagingPipeline(depth=pipeline_depth)
            self._tiers.append(tier)
        self._by_name = {t.name: t for t in self._tiers}
        self.n_slots = slot0                    # fleet-wide lane count
        self.chunk_len = self._tiers[0].chunk_len
        self._delta_sh = (sharding.slot_sharding(mesh)
                          if mesh is not None else None)

        self.pipeline_depth = pipeline_depth
        self.clock = 0.0
        self.clock_dt_s = clock_dt_s
        self.telemetry = telemetry or FleetTelemetry()
        self.tracer = tracer or NULL_TRACER
        self.retired: List[StreamSession] = []

        # -- async ingestion --------------------------------------------------
        self.ingest: Optional[IngestWorker] = None
        if ingest:
            if isinstance(ingest, IngestWorker):
                self.ingest = ingest
            elif isinstance(ingest, IngestConfig):
                self.ingest = IngestWorker(clock_dt_s, ingest)
            else:
                self.ingest = IngestWorker(clock_dt_s)
            if self.ingest._dt != float(clock_dt_s):
                raise ValueError(
                    "ingest worker clock_dt_s disagrees with the "
                    "scheduler's — the virtual-clock replay would diverge")

        # -- adaptive pipeline depth ------------------------------------------
        self.autopilot: Optional[DepthAutopilot] = None
        if autopilot:
            if isinstance(autopilot, DepthAutopilot):
                ap = autopilot
            elif isinstance(autopilot, AutopilotConfig):
                ap = DepthAutopilot(autopilot, tracer=self.tracer)
            else:
                ap = DepthAutopilot(tracer=self.tracer)
            if topology is not None and ap.cfg.max_depth > 1:
                # same drain-safety rule as the constructor clamp above
                ap = DepthAutopilot(
                    dataclasses.replace(ap.cfg, max_depth=1),
                    tracer=ap.tracer)
            ap.note_depth(0, pipeline_depth)
            self.autopilot = ap

        self._refresh_exec_params()

    def _refresh_exec_params(self) -> None:
        """(Re)derive what the chunk fns actually consume from the canonical
        dense ``self.params`` — the mask-free compact rep in compact mode —
        and re-measure the resident serving bytes. Host-side; runs at
        construction and after every topology swap (the only times the base
        weights change).

        Under a mesh the exec params are placed replicated on it every
        time: an epoch leaves them committed to the mesh while the
        constructor's are not, and the chunk fn's jit would trace that
        difference as a new input type (a recompile per first swap)."""
        self._exec_params = (serving_params(self.params, self.cfg)
                             if self.compact else self.params)
        if self.mesh is not None:
            self._exec_params = jax.device_put(
                self._exec_params, sharding.replicated(self.mesh))
        self._params_bytes = sum(
            int(leaf.nbytes)
            for leaf in jax.tree_util.tree_leaves(self._exec_params))
        self._delta_bytes = sum(int(t.deltas.nbytes) for t in self._tiers)

    # -- lifecycle -----------------------------------------------------------
    def submit(self, session: StreamSession,
               tier: Optional[str] = None) -> None:
        """Queue a session for admission at the next stage phase.

        Tier assignment happens here: an explicit ``tier`` argument wins,
        else the session's own ``tier`` attribute, else the first tier.
        An unknown tier name raises before the session touches a grid.
        """
        name = tier or session.tier or self._tiers[0].name
        if name not in self._by_name:
            raise ValueError(
                f"unknown tier {name!r}; have {sorted(self._by_name)}")
        session.tier = name
        session.status = SessionStatus.QUEUED
        if session.n_in is None:
            session.n_in = self.cfg.n_in
        elif session.n_in != self.cfg.n_in:
            # fail here, not mid-step with a half-mutated grid
            raise ValueError(
                f"session {session.sid} n_in={session.n_in} != "
                f"cfg.n_in={self.cfg.n_in}")
        if self.ingest is not None:
            self.ingest.attach(session)
        self._by_name[name].grid.submit(session)

    def close(self) -> None:
        """Stop the ingest worker thread (no-op without one). Safe to call
        more than once; a closed scheduler still drains correctly — the
        drain path falls back to inline steal-polling, which is the serial
        semantics."""
        if self.ingest is not None:
            self.ingest.stop()

    def _replace_lanes(self, tier: _Tier, state, deltas) -> None:
        """Install post-surgery state/deltas on ``tier``, restoring the
        slot sharding — eager ``.at[slot].set`` lane writes are
        single-lane-correct on sharded arrays but may leave the result
        unplaced."""
        if self.mesh is not None:
            state = jax.device_put(state, tier.state_sh)
            deltas = jax.device_put(deltas, self._delta_sh)
        tier.state, tier.deltas = state, deltas

    def _admit(self, tier: _Tier) -> None:
        with self.tracer.span("sched.admit", grid_step=self._staging_step,
                              tier=tier.name) as sp:
            n = 0

            def on_admit(slot: int, sess: StreamSession):
                nonlocal n
                n += 1
                sess.slot, sess.status = slot, SessionStatus.ACTIVE
                self._replace_lanes(tier, *reset_lane(
                    tier.state, tier.deltas, self.cfg, slot))
            tier.grid.admit(on_admit)
            sp.set(admitted=n)

    def _poll_sources(self) -> None:
        """Move newly arrived chunks into session buffers, fleet-wide.

        With an ingest worker this is a lock-protected queue drain — the
        only ingest work left on the critical path; decode/poll cost runs
        on the worker thread. Without one, sources are polled inline (the
        serial reference). Both paths push the same chunks in the same
        per-session order at the same tick (bit-identity pinned in
        tests/test_serving_qos.py)."""
        with self.tracer.span("sched.poll_sources",
                              grid_step=self._staging_step) as sp:
            if self.ingest is not None:
                n, peak = self.ingest.drain(self._staging_step)
                self.telemetry.record_ingest(n, peak)
            else:
                n = 0
                for tier in self._tiers:
                    for sess in (list(tier.grid.occupant)
                                 + list(tier.grid.queue)):
                        if sess is not None and sess.source is not None:
                            for chunk in sess.source.poll(self.clock):
                                sess.push_events(chunk)
                                n += 1
            sp.set(chunks=n)

    @property
    def _staging_step(self) -> int:
        """Grid-step number the *next dispatch* will get (``grid.tick``
        runs at dispatch) — what stage-side spans attribute to."""
        return self._tiers[0].grid.stats["steps"] + 1

    # -- phase 1: stage ------------------------------------------------------
    def _stage(self, tier: _Tier) -> StagedChunk:
        """Host-only assembly of one tier's grid step (no device
        interaction).

        Advances the clock and drains/polls sources (first tier only —
        both are fleet-wide facts), admits into the tier's free lanes,
        packs the event/valid/adapt-mask buffers, and records the step's
        scheduling decisions: which lanes were fed what, which sessions
        exhaust after this step, and which slots are epoch-merge
        eligible. Runs while the previous step's device compute is in
        flight when the pipeline is enabled — this is the overlapped
        phase.
        """
        t0 = time.perf_counter()
        with self.tracer.span("sched.stage", grid_step=self._staging_step,
                              tier=tier.name):
            staged = self._stage_body(tier)
        dt = time.perf_counter() - t0
        self.telemetry.record_phase("stage", dt)
        self.telemetry.record_tier_phase(tier.name, "stage", dt)
        return staged

    def _stage_body(self, tier: _Tier) -> StagedChunk:
        if tier is self._tiers[0]:
            # fleet-wide, once per grid step: the virtual clock and the
            # arrival drain are shared by every tier's stage
            self.clock += self.clock_dt_s
            self._poll_sources()
        self._admit(tier)

        C, S = tier.chunk_len, tier.n_slots
        events = np.zeros((C, S, self.cfg.n_in), np.float32)
        valid = np.zeros((C, S), bool)
        amask = np.zeros(S, bool)
        lanes: List[LaneRecord] = []
        retiring = []
        fed: Dict[int, int] = {}
        for slot, sess in enumerate(tier.grid.occupant):
            if sess is None:
                continue
            chunk = sess.pop_chunk(C)
            n = chunk.shape[0]
            if n:
                events[:n, slot] = chunk
                valid[:n, slot] = True
            amask[slot] = sess.adapt
            fed[slot] = n
            lanes.append(LaneRecord(slot=slot, session=sess, n_fed=n,
                                    events_in=float(chunk.sum())))
            if sess.exhausted:        # a host fact: source done, buffers empty
                retiring.append((slot, sess))
        gone = {slot for slot, _ in retiring}
        merge_slots = tuple(
            slot for slot, sess in enumerate(tier.grid.occupant)
            if sess is not None and sess.adapt and slot not in gone)
        return StagedChunk(events=events, valid=valid, adapt_mask=amask,
                           lanes=lanes, retiring=retiring,
                           merge_slots=merge_slots, fed=fed)

    # -- phase 2: dispatch ---------------------------------------------------
    def _dispatch(self, tier: _Tier, staged: StagedChunk) -> InFlight:
        """Enqueue the tier's chunk fn on the staged buffers —
        asynchronous, no host wait — then free retiring sessions' lanes so
        the *next* stage phase can re-admit into them (same admission
        timing as the serial path, where retire frees lanes before the
        next step's admits)."""
        t0 = time.perf_counter()
        with self.tracer.span("sched.dispatch",
                              grid_step=self._staging_step,
                              tier=tier.name) as sp:
            tier.deltas, tier.state, metrics = tier.chunk_fn(
                self._exec_params, tier.deltas, tier.state, staged.events,
                staged.valid, staged.adapt_mask)
            tier.grid.tick()
            for slot, _ in staged.retiring:
                tier.grid.retire(slot)
            sp.set(lanes=len(staged.lanes), retiring=len(staged.retiring))
            fl = InFlight(staged=staged, deltas=tier.deltas, metrics=metrics,
                          grid_step=tier.grid.stats["steps"])
        dt = time.perf_counter() - t0
        self.telemetry.record_phase("dispatch", dt)
        self.telemetry.record_tier_phase(tier.name, "dispatch", dt)
        return fl

    # -- phase 3: retire -----------------------------------------------------
    def _retire(self, tier: _Tier, fl: InFlight) -> None:
        """Consume one in-flight step: fetch metrics (the only device
        wait), route predictions, fold telemetry, finalize retiring
        sessions from the captured handles, drive the topology service.

        The retire span/phase is attributed to ``fl.grid_step`` — the step
        that *produced* these results — not the step currently staging:
        under pipelining the two differ, and whole-``step()`` wall alone
        cannot say which grid step a retire belonged to.
        """
        t0 = time.perf_counter()
        with self.tracer.span("sched.retire", grid_step=fl.grid_step,
                              tier=tier.name):
            with self.tracer.span("sched.device_wait",
                                  grid_step=fl.grid_step):
                tw0 = time.perf_counter()
                m = jax.device_get(fl.metrics)  # one transfer for all metrics
                wait_s = time.perf_counter() - tw0
            # fl.queued_s: host work done while this step was in flight
            # (stamped by StagingPipeline.push/pop; 0.0 on the serial path)
            ratio = self.telemetry.record_overlap(hidden_s=fl.queued_s,
                                                  wait_s=wait_s)
            if self.autopilot is not None:
                self.telemetry.record_overlap_ema(
                    self.autopilot.observe(ratio))
            self._retire_body(tier, fl, m)
        dt = time.perf_counter() - t0
        self.telemetry.record_phase("retire", dt)
        self.telemetry.record_tier_phase(tier.name, "retire", dt)

    def _retire_body(self, tier: _Tier, fl: InFlight, m) -> None:
        staged = fl.staged
        logits = m.logits                      # [C, S, n_out]
        wend = m.window_end                    # [C, S]
        tsum = {"steps": 0.0, "events_in": 0.0, "sop_forward": 0.0,
                "sop_wu": 0.0, "sop_wu_offered": 0.0, "windows": 0}
        for rec in staged.lanes:
            slot, sess = rec.slot, rec.session
            sess.timesteps_fed += rec.n_fed
            steps = float(m.steps[slot])
            sop_forward = float(m.sop_forward[slot])
            sop_wu = float(m.sop_wu[slot])
            sop_wu_offered = float(m.sop_wu_offered[slot])
            windows = int(wend[:, slot].sum())
            counters = self.telemetry.stream(sess.sid)
            counters.add_chunk(
                steps=steps,
                events_in=rec.events_in,
                sop_forward=sop_forward,
                sop_wu=sop_wu,
                sop_wu_offered=sop_wu_offered,
                gate_opened=float(m.gate_opened[slot].sum()),
                gate_offered=float(m.gate_offered[slot].sum()),
                windows=windows,
                local_loss=float(m.local_loss[slot]))
            tsum["steps"] += steps
            tsum["events_in"] += rec.events_in
            tsum["sop_forward"] += sop_forward
            tsum["sop_wu"] += sop_wu
            tsum["sop_wu_offered"] += sop_wu_offered
            tsum["windows"] += windows
            for t in np.nonzero(wend[:, slot])[0]:
                sess.predictions.append(WindowPrediction(
                    window_idx=len(sess.predictions),
                    logits=logits[t, slot].copy()))
        if staged.lanes:
            self.telemetry.record_tier_chunk(
                tier.name, timesteps=tsum["steps"],
                events_in=tsum["events_in"],
                sop_forward=tsum["sop_forward"], sop_wu=tsum["sop_wu"],
                sop_wu_offered=tsum["sop_wu_offered"],
                windows=tsum["windows"])
        for slot, sess in staged.retiring:
            # the captured post-step handle, NOT tier.deltas: a later stage
            # phase may already have re-admitted into this lane; layout is
            # the fleet's: compact [L, J, T, bk, bo] or dense [L, Kmax, N]
            sess.final_deltas = np.asarray(fl.deltas[slot])
            sess.status, sess.slot = SessionStatus.RETIRED, None
            if self.ingest is not None:
                self.ingest.detach(sess)
            self.retired.append(sess)
        svc = self.topology
        if svc is not None and not svc.frozen and m.pre_mag is not None:
            svc.observe(m)
            self.maybe_evolve_topology(merge_slots=staged.merge_slots,
                                       grid_step=fl.grid_step)

    # -- adaptive depth ------------------------------------------------------
    def _apply_autopilot(self) -> None:
        """Evaluate the depth controller and, on a change, apply it at a
        drain-safe boundary: flush every in-flight step, then resize the
        empty pipelines. Flushing preserves retire order, so the adaptive
        trajectory stays bit-identical to the fixed-depth references —
        only the wall-clock shape of the run changes."""
        step = self._staging_step
        new = self.autopilot.decide(step, self.pipeline_depth)
        if new == self.pipeline_depth:
            return
        with self.tracer.span("autopilot.apply", grid_step=step,
                              depth=self.pipeline_depth, new_depth=new):
            self.flush()
            for tier in self._tiers:
                tier.pipeline.set_depth(new)
        self.pipeline_depth = new
        self.autopilot.note_depth(step, new)
        self.telemetry.record_depth(new, changed=True)

    # -- the one grid step ---------------------------------------------------
    def step(self) -> Dict[int, int]:
        """One grid step across every tier; returns {global slot:
        timesteps fed} for the step staged (and dispatched) by this call
        (tier-local slots offset by the tier's ``slot0``; identical to
        the local ids on a single-tier fleet).

        Serial mode (``pipeline_depth=0``): stage → dispatch → retire per
        tier, all within this call. Pipelined: stage this step
        (overlapping the in-flight device compute), retire the tier's
        oldest in-flight step if its pipeline is full, then dispatch —
        bookkeeping for the staged step lands one ``step()`` later (or at
        :meth:`flush`). With an autopilot attached, depth proposals are
        applied first, at this step boundary.

        Note the whole-step wall time recorded here therefore mixes this
        step's stage/dispatch with an *earlier* step's retire under
        pipelining; per-phase spans and ``telemetry.record_phase`` carry
        the correct per-grid-step attribution (each span's ``grid_step``
        attr names the step that owns the work, and phase sums reconcile
        with step walls — pinned in ``tests/test_obs_serving.py``).
        """
        t0 = time.perf_counter()
        # cached host ints — survives callers swapping self.telemetry
        self.telemetry.record_bytes_held(self._params_bytes,
                                         self._delta_bytes)
        if self.autopilot is not None:
            self._apply_autopilot()
        fed: Dict[int, int] = {}
        with self.tracer.span("sched.step", grid_step=self._staging_step):
            for tier in self._tiers:
                tt0 = time.perf_counter()
                staged = self._stage(tier)
                if tier.pipeline.depth == 0:
                    self._retire(tier, self._dispatch(tier, staged))
                else:
                    while tier.pipeline.full:
                        self._retire(tier, tier.pipeline.pop())
                    tier.pipeline.push(self._dispatch(tier, staged))
                self.telemetry.record_tier_step(
                    tier.name, time.perf_counter() - tt0)
                for slot, n in staged.fed.items():
                    fed[tier.slot0 + slot] = n
        self.telemetry.record_step(time.perf_counter() - t0)
        return fed

    def flush(self) -> None:
        """Retire every in-flight step of every tier (no-op in serial
        mode). Call after the last ``step()`` — predictions, telemetry,
        final-delta snapshots and due topology epochs of in-flight steps
        land here."""
        for tier in self._tiers:
            while len(tier.pipeline):
                t0 = time.perf_counter()
                self._retire(tier, tier.pipeline.pop())
                self.telemetry.record_flush(time.perf_counter() - t0)

    # -- live topology evolution --------------------------------------------
    def maybe_evolve_topology(self, force: bool = False, merge_slots=None,
                              grid_step: Optional[int] = None):
        """Run a due DSST prune/regrow epoch between grid steps.

        The service returns ``(params, deltas)`` with identical shapes and
        slot shardings, so installing them is an atomic swap: active
        sessions keep their lanes and carried state, and the next grid step
        reuses the already-compiled chunk fn (``n_compiles`` stays 1).
        The retire phase passes the staged step's ``merge_slots`` snapshot
        and dispatch-time ``grid_step`` so a pipelined epoch sees exactly
        the fleet the serial scheduler would; manual calls may omit both
        (current occupants / current grid step). Returns the
        ``TopologyEpochEvent`` when an epoch ran, else None.
        """
        svc = self.topology
        tier = self._tiers[0]             # topology fleets are single-tier
        step = tier.grid.stats["steps"] if grid_step is None else grid_step
        if svc is None or not (force or svc.due(step)):
            return None
        if merge_slots is None:
            merge_slots = tuple(
                slot for slot, sess in enumerate(tier.grid.occupant)
                if sess is not None and sess.adapt)
        with self.tracer.span("topology.epoch", grid_step=step,
                              epoch=svc.epoch_idx) as sp:
            params, deltas, event = svc.evolve(
                self.params, tier.deltas, merge_slots=merge_slots,
                grid_step=step)
            sp.set(pruned=event.pruned, regrown=event.regrown,
                   merged=len(event.merged_slots))
        self.params = params
        self._replace_lanes(tier, tier.state, deltas)
        self._refresh_exec_params()   # new mask → new compact wc/idx
        self.telemetry.record_topology_epoch(
            grid_step=event.grid_step, pruned=event.pruned,
            regrown=event.regrown, mask_change=event.mask_change,
            merged_streams=len(event.merged_slots))
        return event

    def run_until_drained(self, max_steps: int = 100_000) -> List[StreamSession]:
        """Step until every submitted session is served, then flush the
        pipeline; returns the retired sessions (bookkeeping complete)."""
        while not all(t.grid.drained for t in self._tiers):
            self.step()
            if self._tiers[0].grid.stats["steps"] >= max_steps:
                break
        self.flush()
        return self.retired

    # -- introspection -------------------------------------------------------
    @property
    def grid(self) -> SlotGrid:
        """The first tier's slot grid (THE grid on a single-tier fleet —
        the long-standing external surface; multi-tier callers iterate
        :attr:`tiers`)."""
        return self._tiers[0].grid

    @property
    def pipeline(self) -> StagingPipeline:
        """The first tier's staging pipeline (every tier's pipeline runs
        at the same depth; this is the inspection handle)."""
        return self._tiers[0].pipeline

    @property
    def chunk_fn(self):
        """The first tier's compiled chunk step."""
        return self._tiers[0].chunk_fn

    @property
    def state(self):
        """The first tier's lane-batched StreamState (the fleet's, on a
        single-tier scheduler)."""
        return self._tiers[0].state

    @state.setter
    def state(self, value):
        self._tiers[0].state = value

    @property
    def deltas(self):
        """The first tier's slot-leading delta tensor."""
        return self._tiers[0].deltas

    @deltas.setter
    def deltas(self, value):
        self._tiers[0].deltas = value

    @property
    def tiers(self) -> Tuple[str, ...]:
        """Tier names, grid order (slot0 ascending)."""
        return tuple(t.name for t in self._tiers)

    def tier_grid(self, name: str) -> SlotGrid:
        """The named tier's slot grid."""
        return self._by_name[name].grid

    @property
    def drained(self) -> bool:
        """True when no session is queued/active on any tier AND no step
        is in flight (i.e. all bookkeeping has landed)."""
        return all(t.grid.drained and len(t.pipeline) == 0
                   for t in self._tiers)

    @property
    def n_compiles(self) -> int:
        """Max per-tier trace count of the slot-grid step (0 before
        warmup, must stay 1 after — the zero-recompilation guarantee,
        per tier). Counted by the chunk fns themselves rather than
        private jit cache internals."""
        return max(t.chunk_fn.n_traces() for t in self._tiers)

    @property
    def n_compiles_by_tier(self) -> Dict[str, int]:
        """Per-tier chunk-fn trace counts (each must be <= 1 after that
        tier's warmup)."""
        return {t.name: t.chunk_fn.n_traces() for t in self._tiers}

    @property
    def utilization(self) -> float:
        """Mean fraction of lanes occupied at dispatch, over all steps
        and tiers (slot-weighted — same formula as SlotGrid.utilization
        on a single-tier fleet)."""
        num = sum(t.grid.stats["slot_busy"] for t in self._tiers)
        den = sum(t.grid.stats["steps"] * t.n_slots for t in self._tiers)
        return num / den if den else 0.0
