"""Slot-multiplexed micro-batching for stateful SNN streams.

``StreamScheduler`` generalizes the continuous batcher's fixed slot grid
(``launch.batching.SlotGrid``) from token decode to SNN timesteps. One
jitted chunk step with static shapes — events ``[chunk_len, n_slots,
ceil(n_in/8)]`` packed spike bits, valid ``[chunk_len, n_slots]`` —
advances every active stream by up to ``chunk_len`` timesteps; admitted
streams claim a lane (reset in place), retired streams free it. Idle or
ragged tails are masked invalid, so they neither perturb state nor accrue
telemetry: an empty slot costs exactly zero counted events.

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
serving/adapt.py), and the admission reset program takes and returns the
slot shardings, so they survive admit/retire.

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
                      make_reset_lanes, nbytes, packed_width)
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
    state/deltas (+ shardings), compiled chunk fn and lane-reset program,
    and staging pipeline.
    ``slot0`` is the tier's offset in the fleet-global slot numbering
    (``step()`` returns global slot ids; everything internal is local).
    ``lane_row[slot]`` is the occupant's row of the telemetry's counter
    table, written at admission (stale on a freed lane until the next)."""

    __slots__ = ("name", "chunk_len", "n_slots", "slot0", "grid", "state",
                 "deltas", "chunk_fn", "reset_lanes", "pipeline", "state_sh",
                 "lane_row")

    def __init__(self, name: str, chunk_len: int, n_slots: int, slot0: int):
        self.name, self.chunk_len = name, chunk_len
        self.n_slots, self.slot0 = n_slots, slot0
        self.state_sh = None
        self.lane_row = np.zeros(n_slots, np.intp)


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
        device_wait``, ``topology.epoch``, ``autopilot.decision/apply``)
        and their sub-spans (``admit.write``, ``dispatch.transfer/
        enqueue``, ``retire.deliver/telemetry/snapshot``; the counter
        fold's ``retire.telemetry`` carries the ``lanes`` it folded); the
        shared no-op ``NULL_TRACER`` by default. Spans
        only time host work the scheduler does anyway — tracing on vs.
        off is bit-identical and leaves the serving jaxpr unchanged
        (pinned in ``tests/test_obs_serving.py``).
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
            if want_factors:
                # a power-of-two shard is an exact subtree of the factor
                # sum's fixed tree (engine.ordered_slot_sum), so each
                # device reduces its own shard and the fleet's sums stay
                # the 1-device bits
                d = sharding.slot_devices(mesh)
                widths = [d * (1 << (w // d - 1).bit_length())
                          for w in widths]
            tier_cfgs = [dataclasses.replace(t, n_slots=w)
                         for t, w in zip(tier_cfgs, widths)]

        self._delta_sh = (sharding.slot_sharding(mesh)
                          if mesh is not None else None)
        self._tiers: List[_Tier] = []
        slot0 = 0
        for tc in tier_cfgs:
            tier = _Tier(tc.name, tc.chunk_len, tc.n_slots, slot0)
            slot0 += tc.n_slots
            tier.grid = SlotGrid(tc.n_slots)
            make_state = lambda n=tc.n_slots: init_stream_state(cfg, n)
            make_deltas = lambda n=tc.n_slots: init_stream_deltas(
                cfg, n, compact=compact)
            if mesh is None:
                tier.state, tier.deltas = make_state(), make_deltas()
            else:
                # made in place on each device's shard: never the whole
                # grid on one device
                tier.state_sh = sharding.stream_shardings(
                    jax.eval_shape(make_state), mesh)
                tier.state = jax.jit(make_state,
                                     out_shardings=tier.state_sh)()
                tier.deltas = jax.jit(make_deltas,
                                      out_shardings=self._delta_sh)()
            # one compiled chunk fn and lane reset per tier (their own [S]
            # static shapes and trace counters); all tiers share cfg/adapt/
            # exec rep
            tier.chunk_fn = make_chunk_fn(cfg, adapt, mesh=mesh,
                                          want_factors=want_factors)
            tier.reset_lanes = make_reset_lanes(
                cfg, compact, state_sh=tier.state_sh,
                delta_sh=self._delta_sh)
            tier.pipeline = StagingPipeline(depth=pipeline_depth)
            self._tiers.append(tier)
        self._by_name = {t.name: t for t in self._tiers}
        self.n_slots = slot0                    # fleet-wide lane count
        self.chunk_len = self._tiers[0].chunk_len
        # the chunk fn's input shardings of events/valid/adapt_mask, for
        # dispatch's explicit host->device put (None: the default device)
        self._input_sh = (sharding.chunk_step_shardings(
            mesh, want_factors)[0][3:] if mesh is not None else None)

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

        if mesh is not None:
            # the dense base replicated on the mesh, as every epoch
            # program returns it (one epoch compile, not two)
            self.params = jax.device_put(params, sharding.replicated(mesh))
        self._refresh_exec_params()

    def _refresh_exec_params(self) -> None:
        """Derive what the chunk fns consume from the canonical dense
        ``self.params`` — the mask-free compact rep in compact mode — and
        measure the resident serving bytes. Runs at construction; a
        topology epoch's program returns the next exec rep itself, with
        the same shapes, so the bytes stay as measured.

        Under a mesh the exec params are placed replicated on it, as the
        epoch program returns them: the chunk fn's jit would trace a
        difference in placement as a new input type (a recompile at the
        first swap)."""
        self._exec_params = (serving_params(self.params, self.cfg)
                             if self.compact else self.params)
        if self.mesh is not None:
            self._exec_params = jax.device_put(
                self._exec_params, sharding.replicated(self.mesh))
        self._params_bytes = sum(
            int(leaf.nbytes)
            for leaf in jax.tree_util.tree_leaves(self._exec_params))
        self._delta_bytes = sum(int(t.deltas.nbytes) for t in self._tiers)

    @property
    def telemetry(self) -> FleetTelemetry:
        """Where the fleet's counters and timings land."""
        return self._telemetry

    @telemetry.setter
    def telemetry(self, telemetry: FleetTelemetry) -> None:
        """Swap the sink: the occupied lanes' counter rows, and those the
        in-flight steps staged, are re-mapped into the new table."""
        self._telemetry = telemetry
        for tier in self._tiers:
            occupied = [(slot, sess.sid)
                        for slot, sess in enumerate(tier.grid.occupant)
                        if sess is not None]
            if occupied:
                slots, sids = zip(*occupied)
                tier.lane_row[list(slots)] = telemetry.rows(sids)
            for fl in tier.pipeline:
                fl.staged.rows = telemetry.rows(
                    [rec.session.sid for rec in fl.staged.lanes])

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

    def _admit(self, tier: _Tier) -> None:
        """Claim free lanes for queued sessions and reset them all in
        place with one call of the tier's lane-reset program (the grids
        are donated to it; no program runs when nobody is admitted).

        A donated delta grid may still be an in-flight step's captured
        handle — at depth >= 1 this stage re-admits lanes that step's
        dispatch freed before its retire has snapshotted them — so those
        steps' retiring lanes are sliced off it first.

        The ``sched.admit`` span counts ``admitted`` sessions, the
        ``programs`` run (0 or 1), the grid ``leaves_written`` by that
        call, and ``bytes_written``: the fresh lanes the program writes
        into the grid, one lane's bytes per admitted session. All shape
        arithmetic, with no device sync; its ``admit.write`` child times
        the program's enqueue."""
        step = self._staging_step
        with self.tracer.span("sched.admit", grid_step=step,
                              tier=tier.name) as sp:
            admitted = tier.grid.admit()
            n = len(admitted)
            programs = leaves = nbytes_out = 0
            if n:
                for slot, sess in admitted:
                    sess.slot, sess.status = slot, SessionStatus.ACTIVE
                tier.lane_row[[slot for slot, _ in admitted]] = \
                    self.telemetry.rows([sess.sid for _, sess in admitted])
                for fl in tier.pipeline:
                    if fl.deltas is tier.deltas:
                        fl.take_snapshots()
                slots = np.full(tier.n_slots, tier.n_slots, np.int32)
                slots[:n] = [slot for slot, _ in admitted]
                with self.tracer.span("admit.write", grid_step=step,
                                      admitted=n):
                    tier.state, tier.deltas = tier.reset_lanes(
                        tier.state, tier.deltas, slots)
                grid = jax.tree_util.tree_leaves((tier.state, tier.deltas))
                programs, leaves = 1, len(grid)
                nbytes_out = n * (nbytes(grid) // tier.n_slots)
            sp.set(admitted=n, programs=programs, leaves_written=leaves,
                   bytes_written=nbytes_out)
        if n:
            self.telemetry.record_admissions(n, nbytes_out)

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
        """Host assembly of one tier's grid step; its only device work is
        admission's asynchronous lane-reset enqueue (no device wait).

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
        # packed-bit rows, as the sessions buffer them; the chunk fn
        # widens them to float32 on the device
        events = np.zeros((C, S, packed_width(self.cfg.n_in)), np.uint8)
        n_fed = np.zeros(S, np.int32)
        amask = np.zeros(S, bool)
        occupied = []
        retiring = []
        for slot, sess in enumerate(tier.grid.occupant):
            if sess is None:
                continue
            chunk = sess.pop_chunk(C)
            n = chunk.shape[0]
            if n:
                events[:n, slot] = chunk
            n_fed[slot] = n
            amask[slot] = sess.adapt
            occupied.append((slot, sess, n))
            if sess.exhausted:        # a host fact: source done, buffers empty
                retiring.append((slot, sess))
        valid = np.arange(C)[:, None] < n_fed      # [C, S]: ragged tails off
        # each lane's exact spike count: the set bits of its column
        spikes = np.bitwise_count(events).sum(axis=0, dtype=np.int32).sum(1)
        lanes = [LaneRecord(slot=slot, session=sess, n_fed=n)
                 for slot, sess, n in occupied]
        slots = np.array([slot for slot, _, _ in occupied], np.intp)
        fed = {slot: n for slot, _, n in occupied}
        gone = {slot for slot, _ in retiring}
        merge_slots = tuple(
            slot for slot, sess in enumerate(tier.grid.occupant)
            if sess is not None and sess.adapt and slot not in gone)
        return StagedChunk(events=events, valid=valid, adapt_mask=amask,
                           lanes=lanes, retiring=retiring,
                           merge_slots=merge_slots, fed=fed, slots=slots,
                           spikes=spikes, rows=tier.lane_row[slots])

    # -- phase 2: dispatch ---------------------------------------------------
    def _dispatch(self, tier: _Tier, staged: StagedChunk) -> InFlight:
        """Put the staged buffers on the device and enqueue the tier's
        chunk fn on them — asynchronous, no host wait — then free retiring
        sessions' lanes so the *next* stage phase can re-admit into them
        (same admission timing as the serial path, where retire frees
        lanes before the next step's admits).

        The device copies of the staged buffers are locals: the chunk fn
        holds them only until it has run, so they never outlive the step
        (an ``InFlight`` keeps the host arrays, not these)."""
        t0 = time.perf_counter()
        step = self._staging_step
        host = (staged.events, staged.valid, staged.adapt_mask)
        h2d = nbytes(host)
        with self.tracer.span("sched.dispatch", grid_step=step,
                              tier=tier.name) as sp:
            with self.tracer.span("dispatch.transfer", grid_step=step):
                events, valid, amask = jax.device_put(host, self._input_sh)
            with self.tracer.span("dispatch.enqueue", grid_step=step):
                tier.deltas, tier.state, metrics = tier.chunk_fn(
                    self._exec_params, tier.deltas, tier.state, events,
                    valid, amask)
            tier.grid.tick()
            for slot, _ in staged.retiring:
                tier.grid.retire(slot)
            sp.set(lanes=len(staged.lanes), retiring=len(staged.retiring),
                   h2d_bytes=h2d)
            fl = InFlight(staged=staged, deltas=tier.deltas, metrics=metrics,
                          grid_step=tier.grid.stats["steps"])
        self.telemetry.record_h2d(h2d)
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
                # one transfer for all metrics; the DSST factors are
                # fetched by retire.factors
                m = jax.device_get(fl.metrics._replace(pre_mag=None,
                                                       post_mag=None))
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
        """Two passes over the step's lanes, then the retiring sessions:
        deliver the window predictions first (a prediction lands as soon
        as its step's results are on the host, not after the counter
        fold), fold the chunk metrics into the per-stream and per-tier
        counters, then snapshot retiring sessions' final deltas."""
        staged = fl.staged
        with self.tracer.span("retire.deliver", grid_step=fl.grid_step):
            logits = m.logits                  # [C, S, n_out]
            wend = m.window_end                # [C, S]
            for rec in staged.lanes:
                slot, sess = rec.slot, rec.session
                sess.timesteps_fed += rec.n_fed
                for t in np.nonzero(wend[:, slot])[0]:
                    sess.predictions.append(WindowPrediction(
                        window_idx=len(sess.predictions),
                        logits=logits[t, slot].copy()))
        with self.tracer.span("retire.telemetry", grid_step=fl.grid_step,
                              lanes=len(staged.slots)):
            self._fold_telemetry(tier, staged, m)
        with self.tracer.span("retire.snapshot", grid_step=fl.grid_step,
                              retiring=len(staged.retiring)) as sp:
            # slices of the captured post-step grid, NOT tier.deltas: a
            # later stage phase may already have re-admitted into these
            # lanes (and then took the slices before donating the grid)
            fl.take_snapshots()
            for slot, sess in staged.retiring:
                # layout is the fleet's: compact [L, J, T, bk, bo] or
                # dense [L, Kmax, N]
                sess.final_deltas = np.asarray(fl.snapshots[slot])
                sess.status, sess.slot = SessionStatus.RETIRED, None
                if self.ingest is not None:
                    self.ingest.detach(sess)
                self.retired.append(sess)
            sp.set(d2h_bytes=nbytes([sess.final_deltas
                                     for _, sess in staged.retiring]))
        svc = self.topology
        if svc is not None and not svc.frozen and \
                fl.metrics.pre_mag is not None:
            with self.tracer.span("retire.factors",
                                  grid_step=fl.grid_step) as sp:
                pre, post = jax.device_get((fl.metrics.pre_mag,
                                            fl.metrics.post_mag))
                sp.set(d2h_bytes=nbytes((pre, post)))
                svc.observe(m._replace(pre_mag=pre, post_mag=post))
            self.maybe_evolve_topology(merge_slots=staged.merge_slots,
                                       grid_step=fl.grid_step)

    def _fold_telemetry(self, tier: _Tier, staged: StagedChunk, m) -> None:
        """Fold the occupied lanes' slices of the chunk metrics into their
        streams' counter rows, and their sums into the tier's: a fixed
        number of array ops, however many lanes. Each stream still gets
        one float64 add per column of the same float32-derived value."""
        s = staged.slots
        if not len(s):
            return
        self.telemetry.fold_streams(
            tier.name, staged.rows, timesteps=m.steps[s],
            events_in=staged.spikes[s], sop_forward=m.sop_forward[s],
            sop_wu=m.sop_wu[s], sop_wu_offered=m.sop_wu_offered[s],
            gate_opened=m.gate_opened[s].sum(1),
            gate_offered=m.gate_offered[s].sum(1),
            windows=m.window_end[:, s].sum(0), local_loss=m.local_loss[s])

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

        The epoch is one program call (``epoch.enqueue``; the delta grid is
        donated to it) whose new base, exec rep and deltas keep their
        shapes and slot shardings, so installing them (``epoch.install``,
        which also fetches the epoch's stats) is an atomic swap: active
        sessions keep their lanes and carried state, and the next grid step
        reuses the already-compiled chunk fn (``n_compiles`` stays 1). The
        ``topology.epoch`` span carries ``pruned``, ``regrown``, ``merged``,
        ``mask_change`` and ``bytes_projected`` (one device's delta shard,
        read and written).
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
            for fl in tier.pipeline:      # the grid is donated below
                if fl.deltas is tier.deltas:
                    fl.take_snapshots()
            with self.tracer.span("epoch.enqueue", grid_step=step):
                run = svc.enqueue(self.params, tier.deltas,
                                  merge_slots=merge_slots, grid_step=step,
                                  mesh=self.mesh)
            with self.tracer.span("epoch.install", grid_step=step):
                self.params, self._exec_params = run.params, run.exec_params
                tier.deltas = run.deltas
                event = svc.resolve(run)
            sp.set(pruned=event.pruned, regrown=event.regrown,
                   merged=len(event.merged_slots),
                   mask_change=event.mask_change,
                   bytes_projected=run.bytes_projected)
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
