"""Live DSST topology evolution under serving traffic.

PR 1–3 froze the N:M topology the moment a fleet started serving: the base
weights and mask were whatever offline training left behind, and only the
per-stream deltas moved.  ElfCore's claim is stronger — dynamic structured
sparse training, online self-supervised learning and activity-dependent
updates run *together* — so this service closes the loop: the connectivity
itself keeps evolving from live activity, without draining a single session.

The cycle, driven by ``StreamScheduler.maybe_evolve_topology()``:

1. **Accumulate** — every grid step the chunk metrics carry DSST factors
   (summed |pre trace| and |OSSL modulator|, computed valid-masked and
   per-slot inside the engine scan, then slot-reduced **on device** by the
   jitted chunk fn with the order-fixed ``engine.ordered_slot_sum`` — the
   host fetches ``pre_mag [L, Kmax]`` / ``post_mag [L, N]``, a few KB,
   instead of a per-step ``[S, L, ·]`` transfer).
   :meth:`TopologyService.observe` folds them into one decaying
   ``DSSTAccumulator`` per layer, stacked — O(K + N) per layer, the chip's
   factorized write-back.
2. **Fold** — hot streams' adaptations are promoted into the shared base
   (``adapt.merge_lane_into_base``, the generic pytree update): the lanes
   with the largest delta norms among the active adaptive slots merge with
   ``merge_weight`` and their lane delta is scaled down by the same factor,
   so a fully-merged lane's *effective* weights are bit-identical across
   the fold.
3. **Evolve** — one stacked prune/regrow epoch via
   ``core.topology.topology_epoch`` — the *same* code path the offline
   train step runs — with ``k`` following the ``DSSTConfig`` decay schedule
   at the service's epoch index.
4. **Remap & swap** — weights keep surviving values bit-exactly (recycled
   coordinates restart at zero) and the slot-sharded delta tensor is
   projected like ``topology.project_deltas`` (survivors bit-exact, pruned
   zeroed), in place, a block of lanes at a time.

Steps 2–4 and the rebuild of the serving exec rep are ONE jitted program
(``jit_topology_epoch`` in a device trace, compiled once per DSST ``k``
level): the hot-lane choice (top ``merge_top`` delta norms among eligible
lanes) happens on the device, the delta grid is donated and comes back in
its slot sharding, and the host only passes the accumulated factors and
the eligible lanes in and installs what comes out. Everything keeps its
shape, dtype and sharding, so the scheduler swaps ``(params, exec rep,
deltas)`` between grid steps with **zero recompilation** of the chunk
step — the exactly-N-per-group invariant is computed in the program and
asserted on the host after every epoch.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import topology as topology_lib
from repro.core.snn import ChunkMetrics, SNNConfig, serving_params

from .adapt import delta_norms, merge_lane_into_base


@dataclasses.dataclass(frozen=True)
class TopologyServiceConfig:
    epoch_every: int = 100       # grid steps between prune/regrow epochs
    accum_decay: float = 0.9     # per-grid-step decay of the pre/post factors
    min_observed_steps: float = 1.0   # valid timesteps required before an epoch
    merge_top: int = 0           # hot streams folded into the base per epoch
    merge_weight: float = 1.0    # fraction of a hot lane's delta promoted
    merge_min_norm: float = 1e-6  # lanes below this delta norm never merge


@dataclasses.dataclass(frozen=True)
class TopologyEpochEvent:
    """What one live prune/regrow epoch did (telemetry record)."""
    epoch: int                   # 0-based epoch index
    grid_step: int               # scheduler step the swap landed after
    pruned: int                  # connections recycled (sum over layers)
    regrown: int
    mask_change: float           # mean fraction of units flipped per layer
    merged_slots: Tuple[int, ...]  # hot lanes folded into the base first


class EpochRecord(NamedTuple):
    """What one epoch program was given and chose, for a caller that
    checks or replays it: the host inputs, and the program's small device
    outputs (fetched by whoever reads them; the delta grid is not here).
    """
    epoch: int
    grid_step: int
    k: Tuple[int, ...]            # recycled per group, per layer
    pre: np.ndarray               # [L, KBmax] accumulated factors given
    post: np.ndarray              # [L, J]
    eligible: np.ndarray          # [S] bool lanes that may merge
    norms: Optional[jax.Array]    # [S] delta norms before the fold
    hot: Optional[jax.Array]      # [merge_top] int32 lanes chosen
    hot_ok: Optional[jax.Array]   # [merge_top] bool: a lane was chosen
    hot_deltas: Optional[jax.Array]  # [merge_top, ...] their deltas


class EpochRun(NamedTuple):
    """One enqueued epoch: the new base, exec rep and delta grid (device
    futures), the program's per-layer stats, and its record."""
    params: Dict[str, Any]
    exec_params: Dict[str, Any]
    deltas: jax.Array
    stats: Tuple[jax.Array, ...]  # pruned [L], regrown [L], change [L], ok
    record: EpochRecord
    bytes_projected: int          # one device's delta shard, read + written


def _take_lanes(d, hot, off):
    """Lanes ``hot`` (global ids) of the local slot block ``d`` that starts
    at global slot ``off``; zeros for a lane held elsewhere."""
    n = d.shape[0]
    loc = hot - off
    inside = (loc >= 0) & (loc < n)
    lanes = jnp.stack([jax.lax.dynamic_index_in_dim(
        d, jnp.clip(i, 0, n - 1), 0, keepdims=False) for i in loc])
    keep = inside.reshape((-1,) + (1,) * (lanes.ndim - 1))
    return jnp.where(keep, lanes, jnp.zeros((), d.dtype))


def _rewrite_local(d, hot, ok, off, weight: float, project, block: int):
    """Rewrite the local slot block ``d`` (global slots from ``off``) in
    place, ``block`` lanes at a time (no second grid): the chosen lanes
    ``hot`` (where ``ok``) keep ``1 - weight`` of their delta (none at
    ``weight >= 1``), then every lane is projected by ``project``."""
    def body(b, d):
        blk = jax.lax.dynamic_slice_in_dim(d, b * block, block, 0)
        lane = off + b * block + jnp.arange(block)
        chosen = ((lane[:, None] == hot[None, :]) & ok[None, :]).any(1)
        kept = (jnp.zeros((), blk.dtype) if weight >= 1.0
                else blk * (1.0 - weight))
        blk = jnp.where(chosen.reshape((-1,) + (1,) * (blk.ndim - 1)),
                        kept, blk)
        return jax.lax.dynamic_update_slice_in_dim(d, project(blk),
                                                   b * block, 0)

    return jax.lax.fori_loop(0, d.shape[0] // block, body, d)


def make_epoch_program(cfg: SNNConfig, service: "TopologyServiceConfig",
                       mesh: Optional[jax.sharding.Mesh] = None):
    """The live epoch as one jitted program.

    Returns ``fn(params, deltas, pre, post, eligible, ks)`` ->
    ``(params', exec_params', deltas', stats, (norms, hot, hot_ok,
    hot_deltas))`` where ``ks`` (static) is the per-layer recycled count
    and ``stats`` is ``(pruned [L], regrown [L], mask_change [L],
    invariant_ok)``. The delta grid is donated and rewritten in place.
    With a ``("slots",)`` mesh the grid keeps its slot
    sharding: each device picks the chosen lanes it holds and projects
    its own shard; only the ``[S]`` norms and the ``merge_top`` chosen
    lanes cross chips. ``fn.n_traces()`` counts compiles."""
    from repro.launch import sharding as SH
    M, weight = service.merge_top, service.merge_weight
    traces = {"n": 0}

    def norms_of(d):
        if mesh is None:
            return delta_norms(d)
        return jax.shard_map(delta_norms, mesh=mesh,
                             in_specs=SH.slot_spec(0),
                             out_specs=SH.slot_spec(0), check_vma=False)(d)

    def take(d, hot):
        if mesh is None:
            return _take_lanes(d, hot, 0)
        from jax.sharding import PartitionSpec as P

        def body(dl, hot):
            off = jax.lax.axis_index(SH.SLOT_AXIS) * dl.shape[0]
            # one device holds each lane and the rest add zeros: exact
            return jax.lax.psum(_take_lanes(dl, hot, off), SH.SLOT_AXIS)
        return jax.shard_map(body, mesh=mesh, in_specs=(SH.slot_spec(0), P()),
                             out_specs=P(), check_vma=False)(d, hot)

    def rewrite(d, hot, ok, project):
        n_local = d.shape[0] // (1 if mesh is None
                                 else SH.slot_devices(mesh))
        block = math.gcd(n_local, 64)
        if mesh is None:
            return _rewrite_local(d, hot, ok, 0, weight, project, block)
        from jax.sharding import PartitionSpec as P

        def body(dl, hot, ok):
            off = jax.lax.axis_index(SH.SLOT_AXIS) * dl.shape[0]
            return _rewrite_local(dl, hot, ok, off, weight, project, block)
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(SH.slot_spec(0), P(), P()),
                             out_specs=SH.slot_spec(0),
                             check_vma=False)(d, hot, ok)

    def topology_epoch(params, deltas, pre, post, eligible, ks):
        traces["n"] += 1
        compact = deltas.ndim == 6
        old_mask = params["hidden"]["mask"]
        norms = hot = ok = lanes = None
        if M > 0:
            norms = norms_of(deltas)
            score = jnp.where(eligible & (norms > service.merge_min_norm),
                              norms, -jnp.inf)
            vals, hot = jax.lax.top_k(score, M)   # ties: lower lane first
            ok = vals > -jnp.inf
            lanes = take(deltas, hot)
            for i in range(M):
                merged = merge_lane_into_base(params, lanes, i, cfg,
                                              weight=weight)
                params = {**params, "hidden": {
                    **params["hidden"], "w": jnp.where(
                        ok[i], merged["hidden"]["w"], params["hidden"]["w"])}}
        new_params, st = topology_lib.topology_epoch(params, pre, post, cfg,
                                                     k=ks)
        new_mask = new_params["hidden"]["mask"]
        if compact:
            old_ids = topology_lib.stacked_kept_ids(old_mask, cfg)
            new_ids = topology_lib.stacked_kept_ids(new_mask, cfg)
            project = lambda blk: topology_lib.project_deltas_compact(
                blk, old_ids, new_ids)
            exec_params = serving_params(new_params, cfg)
        else:
            surv = topology_lib.survivors_dense(old_mask, new_mask, cfg)
            project = lambda blk: jnp.where(surv[None], blk,
                                            jnp.zeros((), blk.dtype))
            exec_params = new_params
        none = (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool))
        deltas = rewrite(deltas, *((hot, ok) if M > 0 else none), project)
        stats = (st.pruned, st.regrown, st.mask_change,
                 topology_lib.invariant_holds(new_mask, cfg))
        return (new_params, exec_params, deltas, stats,
                (norms, hot, ok, lanes))

    jit_kw = {"static_argnums": (5,), "donate_argnums": (1,)}
    if mesh is not None:
        rep, slot = SH.replicated(mesh), SH.slot_sharding(mesh)
        jit_kw["in_shardings"] = (rep, slot, rep, rep, slot)
        jit_kw["out_shardings"] = (rep, rep, slot, rep,
                                   (slot, rep, rep, rep))
    fn = jax.jit(topology_epoch, **jit_kw)
    fn.n_traces = lambda: traces["n"]
    return fn


class TopologyService:
    """Accumulates live DSST factors and evolves the fleet's topology.

    Host-side object: the accumulators are tiny (O(L·(K + N))) numpy
    buffers fed from already-fetched chunk metrics; the epoch itself is
    one jitted program on the scheduler's (possibly slot-sharded) arrays.
    One service instance belongs to one scheduler/fleet.
    """

    def __init__(self, cfg: SNNConfig,
                 service: Optional[TopologyServiceConfig] = None):
        self.cfg = cfg
        self.service = service or TopologyServiceConfig()
        kbs, js = [], []
        for fan_in in cfg.layer_fanins:
            kb, j = cfg.spec(fan_in).unit_counts(fan_in, cfg.n_hidden)
            kbs.append(kb)
            js.append(j)
        self._kbs, self._js = kbs, js
        self._kb_max = max(kbs)
        self._j_max = max(js)
        self.epoch_idx = 0
        self.observed_steps = 0.0
        self._last_epoch_step = 0
        self.events: List[TopologyEpochEvent] = []
        self._programs: Dict[Any, Any] = {}
        self._reset_accumulators()

    def _reset_accumulators(self) -> None:
        # Both factors are accumulated, as the chip writes both back. Note
        # that under the rank-1 factored regrow the within-group ranking
        # depends on |pre| alone (prune_regrow_factored discards the column
        # factor); |post| is carried for parity with the train-path
        # accumulator and for scorers that do consume it (dense-oracle
        # fallback, cross-group tie-breaking).
        L = self.cfg.n_layers
        self.pre = np.zeros((L, self._kb_max), np.float32)
        self.post = np.zeros((L, self._j_max), np.float32)
        self.observed_steps = 0.0

    # -- 1. accumulate --------------------------------------------------------
    def observe(self, metrics: ChunkMetrics) -> None:
        """Fold one grid step's chunk metrics into the decaying factors.

        ``metrics`` is the (host-fetched) ``ChunkMetrics`` of a chunk step;
        ``pre_mag``/``post_mag`` are valid-masked inside the engine, so idle
        slots and ragged tails contribute exactly zero.  The serving chunk
        fn (``adapt.make_chunk_fn(want_factors=True)``) hands them over
        already slot-reduced — ``[L, Kmax]`` / ``[L, N]`` — by the
        order-fixed device-side ``engine.ordered_slot_sum``, whose fixed
        reduction tree is what keeps epoch decisions bit-identical between
        the 1-device and slot-sharded fleets (a bare ``.sum(0)``'s order
        may not match across shardings).  Raw per-slot ``[S, L, ·]``
        factors straight out of ``snn.run_chunk`` are also accepted and
        reduced here on host (np's fixed sequential order).
        """
        if metrics.pre_mag is None:
            raise ValueError(
                "chunk metrics carry no DSST factors (want_factors=False); "
                "a live topology service needs a factor-bearing chunk fn")
        pre = np.asarray(metrics.pre_mag, np.float32)
        post = np.asarray(metrics.post_mag, np.float32)
        if pre.ndim == 3:                      # [S, L, ·]: raw run_chunk form
            pre, post = pre.sum(0), post.sum(0)
        d = self.service.accum_decay
        self.pre *= d
        self.post *= d
        for l, fan_in in enumerate(self.cfg.layer_fanins):
            kb, j = self._kbs[l], self._js[l]
            self.pre[l, :kb] += pre[l, :fan_in].reshape(kb, -1).sum(-1)
            self.post[l, :j] += post[l].reshape(j, -1).sum(-1)
        self.observed_steps += float(np.asarray(metrics.steps).sum())

    @property
    def virtual_step(self) -> int:
        """The host-int step the next epoch presents to the DSST schedule —
        epoch index mapped onto the config's period, so ``frac_decay``/
        ``start_step``/``stop_step`` mean the same thing they do offline."""
        dcfg = self.cfg.dsst
        return dcfg.start_step + self.epoch_idx * max(1, dcfg.period)

    @property
    def frozen(self) -> bool:
        """True when the config says connectivity must not evolve: DSST off,
        dense baseline, or past the RigL-style ``stop_step`` cool-down —
        serve honors the same freeze the train path enforces via
        ``is_update_step``."""
        return (not self.cfg.dsst_enabled or self.cfg.dense
                or self.virtual_step >= self.cfg.dsst.stop_step)

    def due(self, grid_step: int) -> bool:
        """True when the next prune/regrow epoch should run after this grid
        step: connectivity is not frozen, the cadence has elapsed AND enough
        valid traffic was observed (an idle fleet never churns its topology
        on all-zero scores)."""
        if self.frozen:
            return False
        if grid_step - self._last_epoch_step < self.service.epoch_every:
            return False
        return self.observed_steps >= self.service.min_observed_steps

    # -- 2-4. the epoch program ---------------------------------------------
    def program(self, mesh=None):
        """The jitted epoch program for ``mesh`` (one per mesh, built on
        first use; see :func:`make_epoch_program`)."""
        key = id(mesh) if mesh is not None else None
        if key not in self._programs:
            self._programs[key] = (make_epoch_program(
                self.cfg, self.service, mesh=mesh), mesh)
        return self._programs[key][0]

    @property
    def n_program_traces(self) -> int:
        """Compiles of the epoch program, over every mesh."""
        return sum(fn.n_traces() for fn, _ in self._programs.values())

    def level_k(self) -> Tuple[int, ...]:
        """This epoch's recycled count per group, per layer — the static
        key the program compiles once for."""
        step = self.virtual_step
        return tuple(self.cfg.dsst.k_per_group(self.cfg.spec(f), step)
                     for f in self.cfg.layer_fanins)

    def enqueue(self, params: Dict[str, Any], deltas: jax.Array,
                merge_slots: Sequence[int] = (), grid_step: int = 0,
                mesh=None) -> EpochRun:
        """Enqueue one live epoch (asynchronous: no host wait) and advance
        the service's schedule. ``deltas`` is donated to the program.
        Returns the :class:`EpochRun`; :meth:`resolve` reads its stats."""
        if self.frozen:
            raise ValueError(
                "topology is frozen (dsst disabled, dense baseline, or past "
                f"stop_step={self.cfg.dsst.stop_step}); refusing to evolve")
        eligible = np.zeros(deltas.shape[0], bool)
        eligible[list(merge_slots)] = True
        ks = self.level_k()
        pre, post = self.pre.copy(), self.post.copy()
        new_params, exec_params, new_deltas, stats, pick = self.program(
            mesh)(params, deltas, pre, post, eligible, ks)
        devices = 1 if mesh is None else mesh.devices.size
        record = EpochRecord(self.epoch_idx, int(grid_step), ks, pre, post,
                             eligible, *pick)
        self.epoch_idx += 1
        self._last_epoch_step = int(grid_step)
        self._reset_accumulators()
        return EpochRun(new_params, exec_params, new_deltas, stats, record,
                        bytes_projected=2 * int(new_deltas.nbytes) // devices)

    def resolve(self, run: EpochRun) -> TopologyEpochEvent:
        """Fetch an enqueued epoch's stats (waits for the program), check
        the N:M invariant and log the event."""
        pruned, regrown, change, ok, hot, hot_ok = jax.device_get(
            run.stats + (run.record.hot, run.record.hot_ok))
        assert bool(ok), \
            "topology epoch violated the exactly-N-per-group invariant"
        merged = () if hot is None else tuple(
            int(h) for h, o in zip(hot, hot_ok) if o)
        event = TopologyEpochEvent(
            epoch=run.record.epoch, grid_step=run.record.grid_step,
            pruned=int(pruned.sum()), regrown=int(regrown.sum()),
            mask_change=float(change.mean()), merged_slots=merged)
        self.events.append(event)
        return event

    def evolve(self, params: Dict[str, Any], deltas: jax.Array,
               merge_slots: Sequence[int] = (), grid_step: int = 0
               ) -> Tuple[Dict[str, Any], jax.Array, TopologyEpochEvent]:
        """One live topology epoch, synchronously, on a copy of ``deltas``
        (the caller's grid stays valid). Returns ``(params', deltas',
        event)``.

        Shapes, dtypes and (slot-)shardings of both outputs match the
        inputs, so the caller installs them with a plain swap between grid
        steps — no session drains, no recompilation.
        """
        run = self.enqueue(params, jnp.copy(deltas), merge_slots, grid_step)
        return run.params, run.deltas, self.resolve(run)
