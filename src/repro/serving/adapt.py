"""Per-stream online OSSL adaptation under serving load.

Parameter layout: a **frozen shared base** (the trained weights every
stream serves from) plus ONE stacked **per-stream delta** tensor, slot
axis leading, layer axis stacked. The hot-path layout is the compact N:M
tensor ``[n_slots, n_layers, J, T, bk, bo]`` (only kept blocks are
stored — delta memory scales with density); the dense
``[n_slots, n_layers, Kmax, n_hidden]`` layout remains as the A/B
baseline, selected by the rank of whatever ``deltas`` the caller passes.
Each slot's effective weights are
``w_base + delta[slot]``; the activity-dependent gating engine (per-stream
IA/SS thresholds inside ``core.snn.run_chunk``) decides when a stream's
delta absorbs a three-factor OSSL update. A silent or repetitive stream
never pays weight-update energy and never drifts.

This module owns everything *around* the jitted step:

* ``make_chunk_fn`` — jit the chunk step once per (chunk_len, n_slots)
  geometry; the returned callable is the single compiled artifact the
  scheduler drives (compilation-count checked in the serving benchmark);
* per-stream adapt on/off (``adapt_mask``) applied by freezing a lane's
  delta across the step — exactly equivalent to gating the update off,
  while trace/threshold state keeps tracking the stream;
* delta hygiene: multiplicative decay toward the base and a hard clip,
  applied only to lanes that actually processed valid timesteps this chunk
  (an idle slot keeps its delta bit-identical — the scheduler's "empty slot
  costs exactly zero" invariant), so hours-long streams cannot diverge;
* slot-axis sharding: pass a ``("slots",)`` mesh
  (``launch.mesh.make_serving_mesh``) and the chunk step runs under
  ``shard_map`` with slot-leading ``NamedSharding`` on every per-stream
  tensor — each device advances only its slot shard, with zero
  cross-device collectives (the step is per-slot separable by
  construction; asserted in ``core/engine.scan_chunk``);
* ``merge_lane_into_base`` — promote one stream's adaptation into the
  shared base (fleet learning; the hook for DSST-under-traffic later).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import engine
from repro.core.snn import ChunkMetrics, SNNConfig, StreamState, run_chunk


@dataclasses.dataclass(frozen=True)
class AdaptConfig:
    enabled: bool = True
    delta_decay: float = 1.0     # per-chunk multiplicative decay (1.0 = off)
    delta_clip: float = 0.5      # hard |delta| bound (0 = off)
    lr_scale: float = 1.0        # scales cfg.lr for the serving path


def make_chunk_fn(cfg: SNNConfig, adapt: AdaptConfig | None = None,
                  mesh: Optional[jax.sharding.Mesh] = None,
                  want_factors: bool = True):
    """Build the jitted slot-grid step.

    Returns ``fn(params, deltas, state, events, valid, adapt_mask)`` ->
    ``(deltas, state, metrics)`` with static shapes: ``events`` [C, S, n_in],
    ``valid`` [C, S] bool, ``adapt_mask`` [S] bool. One compilation serves
    any number of streams multiplexed through the S slots.

    With ``mesh`` (a 1-D ``("slots",)`` mesh), the step runs under
    ``shard_map`` with explicit slot-leading in/out shardings: ``deltas``,
    every ``StreamState`` leaf and ``adapt_mask`` shard their slot axis,
    the ``[C, S, ...]`` event/valid buffers shard axis 1, params replicate.
    Each device advances only its slot shard — no collectives — so the
    result is bit-identical to the single-device path. S must divide by the
    mesh's device count (``launch.sharding.check_slot_divisible``).

    ``want_factors`` (static) controls the DSST activity factors the live
    topology service consumes:

    * ``True`` (default) — the engine accumulates per-slot ``pre_mag``/
      ``post_mag`` over the chunk and this wrapper slot-reduces them **on
      device** with the order-fixed ``engine.ordered_slot_sum`` before they
      leave the jit: the metrics carry ``[L, Kmax]`` / ``[L, N]`` (a few
      KB) instead of a per-step ``[S, L, ·]`` device→host transfer. Under
      a mesh each device reduces its own slot shard inside the
      ``shard_map`` (one ``[1, L, ·]`` partial each) and the ``[D, L, ·]``
      partials are combined by the same tree's top levels, so only the
      partials cross chips and, with a power-of-two shard, the sums are
      the 1-device fleet's bits.
    * ``False`` — the accumulators are compiled out of the chunk scan
      entirely (``metrics.pre_mag is None``); the O(S·(K+N))-per-timestep
      in-scan cost disappears. Use for fleets with a frozen topology.
    """
    adapt = adapt or AdaptConfig()
    scfg = cfg if adapt.lr_scale == 1.0 else dataclasses.replace(
        cfg, lr=cfg.lr * adapt.lr_scale)
    traces = {"n": 0}   # bumps once per (re)trace — public-API compile count

    def step(params, deltas, state: StreamState, events, valid, adapt_mask
             ) -> Tuple[jax.Array, StreamState, ChunkMetrics]:
        new_deltas, new_state, metrics = run_chunk(
            params, deltas, state, events, valid, scfg, learn=adapt.enabled,
            want_factors=want_factors)
        d = new_deltas                           # [S, L, ...] either layout
        if adapt.delta_decay < 1.0:
            d = d * adapt.delta_decay
        if adapt.delta_clip > 0.0:
            d = jnp.clip(d, -adapt.delta_clip, adapt.delta_clip)
        # decay/clip only touch lanes that processed valid timesteps this
        # chunk; frozen AND idle lanes keep their old delta bit-exactly
        live = adapt_mask & valid.any(0)         # [S]
        out = jnp.where(live.reshape((-1,) + (1,) * (d.ndim - 1)), d, deltas)
        # a frozen lane is not billed for weight updates — and is not
        # *offered* any either, or its wu_skip_rate reads a fake 100%
        metrics = metrics._replace(
            sop_wu=metrics.sop_wu * adapt_mask,
            sop_wu_offered=metrics.sop_wu_offered * adapt_mask,
            gate_opened=metrics.gate_opened * adapt_mask[:, None],
            gate_offered=metrics.gate_offered * adapt_mask[:, None])
        if want_factors and mesh is not None:
            # this device's slot shard, reduced here: a [1, L, ·] partial
            metrics = metrics._replace(
                pre_mag=engine.ordered_slot_sum(metrics.pre_mag)[None],
                post_mag=engine.ordered_slot_sum(metrics.post_mag)[None])
        return out, new_state, metrics

    if mesh is None:
        body, jit_kw = step, {}
        validate = lambda n_slots: None
    else:
        from repro.launch import sharding as SH
        in_specs, out_specs = SH.chunk_step_specs(want_factors)
        body = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)
        in_sh, out_sh = SH.chunk_step_shardings(mesh, want_factors)
        jit_kw = {"in_shardings": in_sh, "out_shardings": out_sh}
        validate = lambda n_slots: SH.check_slot_divisible(n_slots, mesh)

    @functools.partial(jax.jit, **jit_kw)
    def chunk_fn(params, deltas, state, events, valid, adapt_mask):
        traces["n"] += 1
        validate(events.shape[1])   # trace-time: clean error, not XLA's
        deltas, state, metrics = body(params, deltas, state, events, valid,
                                      adapt_mask)
        if want_factors:
            # order-fixed reduction of the [S, L, ·] factors (one device) or
            # of the [D, L, ·] shard partials (a mesh) on device: the
            # topology service fetches O(L·(K+N)), not O(S·L·(K+N))
            metrics = metrics._replace(
                pre_mag=engine.ordered_slot_sum(metrics.pre_mag),
                post_mag=engine.ordered_slot_sum(metrics.post_mag))
        return deltas, state, metrics

    chunk_fn.n_traces = lambda: traces["n"]
    chunk_fn.mesh = mesh
    chunk_fn.want_factors = want_factors
    return chunk_fn


def delta_norms(deltas: jax.Array) -> jax.Array:
    """Per-slot L2 norm of the adaptation, summed over layers. [S].

    ``deltas``: the stacked slot-leading per-stream tensor, compact
    ``[S, L, J, T, bk, bo]`` or dense ``[S, L, Kmax, N]``. Compact storage
    holds only kept coordinates and dense deltas are zero off-mask, so the
    two layouts report the same norms.
    """
    sq = (deltas * deltas).sum(axis=tuple(range(2, deltas.ndim)))
    return jnp.sqrt(sq).sum(1)


def merge_lane_into_base(params: Dict[str, Any], deltas: jax.Array, slot: int,
                         cfg: SNNConfig, weight: float = 1.0) -> Dict[str, Any]:
    """Fold stream ``slot``'s delta into the shared base weights — mask-free.

    No dense mask is rebuilt: a compact lane scatters its kept blocks into
    the base (pruned coordinates untouched — the base is exactly zero there
    by the topology invariant), and a dense lane is zero off-mask by the
    same invariant, so a plain add preserves base sparsity bit-exactly
    (the TopologyService fold-exactness property). Only ``hidden/w`` is
    rebuilt — every other key in ``params`` (present or added by a future
    PR) rides through the generic dict update untouched. The serving
    topology service reuses this as its fold-hot-streams step.
    """
    lane = deltas[slot]
    if lane.ndim == 5:               # compact [L, J, T, bk, bo]
        from repro.core import topology as topology_lib
        idx = topology_lib.stacked_kept_ids(params["hidden"]["mask"], cfg)
        lane = engine.densify_deltas(lane[None], idx, cfg)[0]
    w = params["hidden"]["w"] + weight * lane
    return {**params, "hidden": {**params["hidden"], "w": w}}
