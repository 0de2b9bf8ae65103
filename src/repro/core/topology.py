"""First-class layer-stacked N:M topology lifecycle — shared by train & serve.

Before this module the sparsity *topology* of the network was scattered:
``sparsity.py`` owned per-layer mask construction, ``dsst.py`` owned the
per-layer prune/regrow event, ``engine.py``'s param dict carried the stacked
``[L, KBmax, J]`` mask, and the training loop in ``snn.run_sample`` hand-
rolled the per-layer epoch while the serving runtime froze connectivity
forever.  ``Topology`` makes the stacked mask (plus its compact kept-unit
index view — the chip's 9-bit index SRAM) a value with a lifecycle:

* :func:`topology_epoch` — ONE stacked prune/regrow epoch over every hidden
  layer, used verbatim by the offline training step (``snn.run_sample``) and
  the live serving topology service (``serving/topology_service.py``).  It
  honors the ``DSSTConfig`` decay schedule trace-safely: a host-int step
  resolves ``k`` directly; a traced step dispatches over the static schedule
  levels with ``lax.switch`` (see :func:`repro.core.dsst.scheduled_k_apply`).
* :func:`project_deltas` — remap the slot-sharded ``[S, L, Kmax, N]``
  per-stream delta tensor across a mask change: surviving connections keep
  their delta values **bit-exactly** (``jnp.where``, not a multiply), pruned
  and regrown coordinates restart at zero.  Same shapes in and out, so a
  topology swap never recompiles the serving chunk step.
* :func:`prune_regrow_stacked` / :func:`prune_regrow_factored_stacked` —
  vmapped-over-layers forms of the core DSST events, also reused by the
  LM-scale DSST pass (``optim/sparse.lm_dsst_event``).

Layer stacking follows the engine convention: masks are padded with
``False`` rows up to the stack width ``Kmax``; all topology math slices each
layer back to its true ``(KB, J)`` before grouping, so padded rows can never
be pruned into or regrown from.  When every layer shares one fan-in (the
paper's 512-512 configuration) the epoch runs as a single vmap over the
layer axis; otherwise it falls back to an equivalent per-layer loop — one
code path, two lowerings.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .dsst import prune_regrow, prune_regrow_factored, scheduled_k_apply
from .sparsity import (NMSpec, check_unit_mask, compact_indices,
                       expand_unit_mask, unit_scores)


# ---------------------------------------------------------------------------
# the topology value
# ---------------------------------------------------------------------------

class Topology(NamedTuple):
    """Stacked N:M connectivity of every hidden layer.

    ``unit_mask``: bool ``[L, Kmax(=KBmax·block), J]`` — the same padded
    layout ``params["hidden"]["mask"]`` carries (False rows above a layer's
    true unit count). ``idx``: int32 ``[L, G, n, J]`` compact kept-unit ids
    per group — the value/index SRAM pair's index half; present only for
    uniform layer geometry (``None`` otherwise, where per-layer group shapes
    differ and a single stacked index tensor does not exist).
    """
    unit_mask: jax.Array
    idx: Optional[jax.Array]


class TopologyStats(NamedTuple):
    """Per-layer epoch telemetry: int32 ``[L]`` pruned/regrown, f32 ``[L]``
    mask-change fraction."""
    pruned: jax.Array
    regrown: jax.Array
    mask_change: jax.Array

    @property
    def total_pruned(self):
        return self.pruned.sum()

    @property
    def total_regrown(self):
        return self.regrown.sum()


def specs(cfg) -> Tuple[NMSpec, ...]:
    """Per-layer N:M specs (one per hidden layer, in stack order)."""
    return tuple(cfg.spec(f) for f in cfg.layer_fanins)


def uniform_geometry(cfg) -> bool:
    return len(set(cfg.layer_fanins)) == 1


def _k_max(cfg) -> int:
    return max(cfg.layer_fanins)


def _pad_rows(x: jax.Array, k: int) -> jax.Array:
    if x.shape[0] == k:
        return x
    return jnp.pad(x, ((0, k - x.shape[0]),) + ((0, 0),) * (x.ndim - 1))


def layer_mask(mask_stacked: jax.Array, l: int, cfg) -> jax.Array:
    """Layer ``l``'s true ``[KB, J]`` unit mask out of the padded stack."""
    spec = cfg.spec(cfg.layer_fanins[l])
    kb, j = spec.unit_counts(cfg.layer_fanins[l], cfg.n_hidden)
    return mask_stacked[l, :kb, :j]


def from_mask(mask_stacked: jax.Array, cfg) -> Topology:
    """Wrap a stacked padded mask, building the compact index view when the
    layer geometry is uniform."""
    idx = None
    if uniform_geometry(cfg):
        spec = cfg.spec(cfg.layer_fanins[0])
        idx = jax.vmap(lambda m: compact_indices(m, spec))(mask_stacked)
    return Topology(unit_mask=mask_stacked, idx=idx)


def from_params(params: Dict[str, Any], cfg) -> Topology:
    return from_mask(params["hidden"]["mask"], cfg)


def install(topo: Topology, params: Dict[str, Any]) -> Dict[str, Any]:
    """Return ``params`` with the topology's mask installed — a generic
    pytree update that preserves every other key at both nesting levels."""
    return {**params,
            "hidden": {**params["hidden"], "mask": topo.unit_mask}}


def invariant_holds(mask: jax.Array, cfg) -> jax.Array:
    """The N:M invariant of a stacked mask as a bool scalar (traceable):
    every layer keeps exactly n units per (group, out-tile) and padded
    rows stay all-False."""
    if uniform_geometry(cfg):        # no padding: one stacked check
        return check_unit_mask(mask, cfg.spec(cfg.layer_fanins[0]))
    ok = jnp.asarray(True)
    for l, fan_in in enumerate(cfg.layer_fanins):
        spec = cfg.spec(fan_in)
        kb, j = spec.unit_counts(fan_in, cfg.n_hidden)
        ok = ok & check_unit_mask(mask[l, :kb, :j], spec) \
            & ~mask[l, kb:].any()
    return ok


def check(mask_or_topo: Union[Topology, jax.Array], cfg) -> bool:
    """Host-side form of :func:`invariant_holds`."""
    mask = mask_or_topo.unit_mask if isinstance(mask_or_topo, Topology) \
        else mask_or_topo
    return bool(invariant_holds(jnp.asarray(np.asarray(mask)), cfg))


def dense_masks(mask_stacked: jax.Array, cfg, dtype=jnp.float32) -> jax.Array:
    """Stacked unit masks ``[L, KBmax, J]`` -> dense ``[L, Kmax, N]`` (zero
    rows where a layer's fan-in is below the stack width)."""
    k_max = _k_max(cfg)
    cols = []
    for l, fan_in in enumerate(cfg.layer_fanins):
        spec = cfg.spec(fan_in)
        kb, j = spec.unit_counts(fan_in, cfg.n_hidden)
        d = expand_unit_mask(mask_stacked[l, :kb, :j], spec, fan_in,
                             cfg.n_hidden)
        cols.append(_pad_rows(d.astype(dtype), k_max))
    return jnp.stack(cols)


# ---------------------------------------------------------------------------
# stacked prune/regrow (vmapped over the layer axis)
# ---------------------------------------------------------------------------

def prune_regrow_stacked(unit_mask: jax.Array, weight_score: jax.Array,
                         grad_score: jax.Array, spec: NMSpec, k: int
                         ) -> Tuple[jax.Array, TopologyStats]:
    """Dense-oracle DSST event for a ``[L, KB, J]`` mask stack sharing one
    spec — one vmap instead of L traces."""
    new_mask, st = jax.vmap(
        lambda m, w, g: prune_regrow(m, w, g, spec, k)
    )(unit_mask, weight_score, grad_score)
    return new_mask, TopologyStats(st.pruned, st.regrown, st.mask_change)


def prune_regrow_factored_stacked(unit_mask: jax.Array,
                                  weight_score: jax.Array,
                                  pre_score: jax.Array, post_score: jax.Array,
                                  spec: NMSpec, k: int
                                  ) -> Tuple[jax.Array, TopologyStats]:
    """Factored (neuron-level-sorted) DSST event for a mask stack:
    ``pre_score [L, KB]``, ``post_score [L, J]``."""
    new_mask, st = jax.vmap(
        lambda m, w, p, q: prune_regrow_factored(m, w, p, q, spec, k)
    )(unit_mask, weight_score, pre_score, post_score)
    return new_mask, TopologyStats(st.pruned, st.regrown, st.mask_change)


# ---------------------------------------------------------------------------
# delta / weight remapping across a mask change
# ---------------------------------------------------------------------------

def survivors_dense(old_mask: jax.Array, new_mask: jax.Array, cfg,
                    dtype=jnp.bool_) -> jax.Array:
    """Dense ``[L, Kmax, N]`` mask of connections present in BOTH masks."""
    return dense_masks(old_mask & new_mask, cfg, dtype=dtype)


def stacked_kept_ids(mask_stacked: jax.Array, cfg) -> jax.Array:
    """Stacked kept-block ids ``[L, J, T]`` — the same argsort convention as
    ``kernels/nm_spmm.make_compact`` (ascending kept block ids per out
    tile), so ids derived here address compact tensors built there.
    Uniform geometry only (one ``T`` shared by every layer)."""
    if not uniform_geometry(cfg):
        raise ValueError("stacked kept ids require uniform layer fan-in "
                         f"(got {tuple(cfg.layer_fanins)})")
    spec = cfg.spec(cfg.layer_fanins[0])
    kb, _ = spec.unit_counts(cfg.layer_fanins[0], cfg.n_hidden)
    t = (kb // spec.m) * spec.n
    idx = jnp.argsort(~mask_stacked, axis=1, stable=True)[:, :t, :]
    return idx.transpose(0, 2, 1).astype(jnp.int32)           # [L, J, T]


def project_deltas_compact(deltas_c: jax.Array, old_ids: jax.Array,
                           new_ids: jax.Array) -> jax.Array:
    """Remap compact per-stream deltas ``[S, L, J, T, bk, bo]`` from the old
    topology's kept-block ids to the new one's (both ``[L, J, T]``).

    A pure gather: every new slot that addresses a surviving block copies
    the old slot's bits unchanged; regrown blocks start at zero. No dense
    tensor is ever built — the epoch-boundary analogue of the mask-free
    hot path.
    """
    eq = new_ids[..., :, None] == old_ids[..., None, :]       # [L, J, T, T]
    hit = eq.any(-1)                                          # [L, J, T]
    pos = jnp.argmax(eq, axis=-1)                             # [L, J, T]
    gathered = jnp.take_along_axis(
        deltas_c, pos[None, :, :, :, None, None], axis=3)
    return jnp.where(hit[None, :, :, :, None, None], gathered,
                     jnp.zeros((), deltas_c.dtype))


def project_deltas(deltas: jax.Array, old_mask: jax.Array,
                   new_mask: jax.Array, cfg) -> jax.Array:
    """Remap the per-stream delta tensor across a mask change: surviving
    connections keep their values bit-exactly, pruned and regrown
    coordinates go to zero (regrown restart clean, as on-chip).

    Dispatches on layout: compact ``[S, L, J, T, bk, bo]`` deltas remap by
    a kept-block-id gather (no dense tensor materialised); dense
    ``[S, L, Kmax, N]`` deltas use a ``jnp.where`` against the dense
    survivor mask (not a mask multiply) so survivors are the identical
    bits — the acceptance property of the zero-recompile topology swap.
    """
    if deltas.ndim == 6:
        return project_deltas_compact(deltas,
                                      stacked_kept_ids(old_mask, cfg),
                                      stacked_kept_ids(new_mask, cfg))
    surv = survivors_dense(old_mask, new_mask, cfg)           # [L, Kmax, N]
    return jnp.where(surv[None], deltas, jnp.zeros((), deltas.dtype))


def remap_weights(w_stacked: jax.Array, old_mask: jax.Array,
                  new_mask: jax.Array, cfg) -> jax.Array:
    """Stacked form of ``dsst.apply_dsst_to_weights``: survivors keep their
    values bit-exactly; pruned and regrown entries are zeroed."""
    surv = survivors_dense(old_mask, new_mask, cfg)
    return jnp.where(surv, w_stacked, jnp.zeros((), w_stacked.dtype))


def weight_unit_scores(w_stacked: jax.Array, cfg) -> jax.Array:
    """|w| summarised to unit granularity per layer: ``[L, KBmax, J]``
    (padded rows score 0 — they are structurally unprunable anyway)."""
    k_max = _k_max(cfg)
    cols = []
    for l, fan_in in enumerate(cfg.layer_fanins):
        spec = cfg.spec(fan_in)
        kb, j = spec.unit_counts(fan_in, cfg.n_hidden)
        s = unit_scores(w_stacked[l, :fan_in, :], spec, fan_in, cfg.n_hidden)
        cols.append(_pad_rows(s, k_max))
    return jnp.stack(cols)


# ---------------------------------------------------------------------------
# THE shared epoch (train == serve)
# ---------------------------------------------------------------------------

def topology_epoch(params: Dict[str, Any], pre: jax.Array, post: jax.Array,
                   cfg, step: Union[int, jax.Array] = 0,
                   k: Optional[Tuple[int, ...]] = None
                   ) -> Tuple[Dict[str, Any], TopologyStats]:
    """One stacked DSST prune/regrow epoch over every hidden layer.

    ``pre``: unit-granular ``[L, KBmax]`` pre-synaptic activity factors
    (padded rows ignored), ``post``: ``[L, J]`` post factors — the
    ``DSSTAccumulator`` contents, stacked.  ``step`` selects the recycled
    count ``k`` from ``cfg.dsst``'s decay schedule: a host int resolves it
    statically, a traced array dispatches over the precomputed schedule
    levels (trace-safe — see ``DSSTConfig.k_levels``). A static per-layer
    ``k`` given by the caller (the serving epoch program, compiled once per
    schedule level) takes the place of the schedule.

    Returns ``(new_params, stats)``; ``new_params`` has the evolved mask
    installed and weights remapped (survivors bit-exact, recycled zeroed),
    every other param leaf untouched.  Used by ``snn.run_sample`` (offline
    epochs inside the jitted train step) and by
    ``serving.topology_service.TopologyService`` (live epochs between grid
    steps) — train and serve share this one prune/regrow code path.
    """
    mask = params["hidden"]["mask"]
    w = params["hidden"]["w"]
    wscore = weight_unit_scores(w, cfg)

    def at_k(l, spec, fn):
        return fn(k[l]) if k is not None else scheduled_k_apply(
            step, cfg.dsst, spec, fn)

    if uniform_geometry(cfg):
        spec = cfg.spec(cfg.layer_fanins[0])
        new_mask, stats = at_k(
            0, spec, lambda k: prune_regrow_factored_stacked(
                mask, wscore, pre, post, spec, k))
    else:
        new_masks, per_layer = [], []
        for l, fan_in in enumerate(cfg.layer_fanins):
            spec = cfg.spec(fan_in)
            kb, j = spec.unit_counts(fan_in, cfg.n_hidden)
            nm, st = at_k(
                l, spec,
                lambda k, l=l, spec=spec, kb=kb, j=j: prune_regrow_factored(
                    mask[l, :kb, :j], wscore[l, :kb, :j],
                    pre[l, :kb], post[l, :j], spec, k))
            new_masks.append(_pad_rows(nm, mask.shape[1]))
            per_layer.append(st)
        new_mask = jnp.stack(new_masks)
        stats = TopologyStats(
            pruned=jnp.stack([s.pruned for s in per_layer]),
            regrown=jnp.stack([s.regrown for s in per_layer]),
            mask_change=jnp.stack([s.mask_change for s in per_layer]))

    new_w = remap_weights(w, mask, new_mask, cfg)
    new_params = install(Topology(new_mask, None), params)
    new_params = {**new_params,
                  "hidden": {**new_params["hidden"], "w": new_w}}
    return new_params, stats
