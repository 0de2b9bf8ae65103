"""Logical-axis sharding rules (MaxText-style) for every pool architecture.

Megatron-pattern tensor parallelism on "model", data parallelism on
("pod","data"):

* embeddings / lm_head: vocab on "model" (sharded softmax cross-entropy);
* attention QKV column-parallel (heads on "model"), O row-parallel;
* MLP up column-parallel, down row-parallel (one all-reduce per block);
* MoE: EP (experts on "model") for many-small-expert configs, TP-inside-
  expert (d_ff on "model") for few-big-expert configs (configs decide);
* Mamba2: in/out projections column/row-parallel; recurrent state sharded
  on the head-dim axis (P) — head count (80) is not divisible by 16, P=64 is;
* KV caches: batch on DP axes, head_dim on "model";
* N:M kept-row index tables: replicated (tiny int32);
* norms/scalars: replicated.

Rules are matched on the path *suffix*; leaves under stacked subtrees
("layers", "local_heads") automatically get a leading ``None`` for the layer
dim, expert tensors get one for E, etc., by right-aligning the rule with the
leaf rank. Divisibility is checked and demoted to replication with a warning
(a rule that silently no-ops is a bug magnet; the dry-run prints demotions).
"""
from __future__ import annotations

import logging
import re
from typing import Any, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from .mesh import dp_axes

log = logging.getLogger(__name__)


def _rules(cfg: ModelConfig) -> Sequence[Tuple[str, Tuple]]:
    """(path regex, right-aligned partition tuple). First match wins."""
    if cfg.moe_shard_experts:      # EP: experts on model
        moe_mat = ("model", None, None)
    else:                          # TP inside experts
        moe_up = (None, None, "model")
        moe_dn = (None, "model", None)
    r: list = [
        # alternatives: first fully-divisible option wins. Embedding prefers
        # d_model sharding: a vocab-sharded table turns the token gather into
        # a full-table all-gather (§Perf, decode cells); D-sharded gathers
        # locally and the [B, D/16] result reshards for free.
        (r"embed/tok$", [(None, "model"), ("model", None)]),
        (r"embed/frontend_proj$", (None, "model")),
        (r"lm_head$", [(None, "model"), ("model", None)]),
        (r"(wq|wk|wv)/w$", (None, "model")),
        (r"(wq|wk|wv)/rows$", (None,)),
        (r"wo/w$", ("model", None)),
        (r"moe/router$", (None, None)),
    ]
    if cfg.family == "moe":
        if cfg.moe_shard_experts:
            r += [(r"moe/(w1|w3|w2)/w$", moe_mat)]
        else:
            r += [(r"moe/(w1|w3)/w$", moe_up), (r"moe/w2/w$", moe_dn)]
    r += [
        (r"(w1|w3)/w$", (None, "model")),
        (r"w2/w$", ("model", None)),
        (r"rows$", (None,)),
        (r"umask$", (None, None)),
        (r"mixer/in_proj/w$", (None, "model")),
        (r"mixer/out_proj/w$", ("model", None)),
        (r"mixer/conv_w$", (None, "model")),
        (r"mixer/conv_b$", ("model",)),
        (r"mixer/norm_g$", ("model",)),
        (r"mixer/(a_log|d_skip|dt_bias)$", (None,)),
        (r"local_heads/p$", (None, "model")),
        (r"(norm1|norm2|final_norm|norm_g)$", (None,)),
    ]
    return r


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def spec_for(path_str: str, shape: Tuple[int, ...], cfg: ModelConfig,
             mesh: Mesh) -> P:
    base: Optional[Any] = None
    for pat, spec in _rules(cfg):
        if re.search(pat, path_str):
            base = spec
            break
    candidates = base if isinstance(base, list) else [base if base is not None else ()]

    def fit(b) -> Tuple[P, bool]:
        # right-align: leading stacked dims (layers L, experts E, …) replicate
        full = (None,) * (len(shape) - len(b)) + tuple(b)
        full = full[-len(shape):] if shape else ()
        fixed, clean = [], True
        for dim, ax in zip(shape, full):
            if ax is None:
                fixed.append(None)
            elif dim % mesh.shape[ax] == 0:
                fixed.append(ax)
            else:
                fixed.append(None)
                clean = False
        return P(*fixed), clean

    first = None
    for cand in candidates:
        p, clean = fit(cand)
        if first is None:
            first = p
        if clean:
            return p
    log.warning("demoted sharding for %s %s -> %s", path_str, shape, first)
    return first


def tree_shardings(tree: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    """ShapeDtypeStruct/array tree -> NamedSharding tree (same structure)."""
    def one(path, leaf):
        if np.ndim(leaf) == 0 or not hasattr(leaf, "shape"):
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec_for(_path_str(path), leaf.shape, cfg, mesh))
    return jax.tree_util.tree_map_with_path(one, tree)


# ---------------------------------------------------------------------------
# activations / batch / cache
# ---------------------------------------------------------------------------

def opt_state_shardings(opt_tree: Any, params_tree: Any, cfg: ModelConfig,
                        mesh: Mesh) -> Any:
    """ZeRO-1: optimizer moments additionally shard one spare dim over the
    DP axes. Params stay DP-replicated; XLA turns the moment update into a
    per-DP-slice computation plus one param-sized gather — the classic
    ZeRO-1 exchange. Cuts Adam-state memory by the DP width (§Perf,
    deepseek train: 33.7 -> 2.1 GB/device)."""
    axes = dp_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1

    def one(path, leaf):
        if np.ndim(leaf) == 0 or not hasattr(leaf, "shape"):
            return NamedSharding(mesh, P())
        base = spec_for(_path_str(path), leaf.shape, cfg, mesh)
        if total <= 1:
            return NamedSharding(mesh, base)
        spec = list(base) + [None] * (len(leaf.shape) - len(base))
        for i, (dim, ax) in enumerate(zip(leaf.shape, spec)):
            if ax is None and dim % total == 0 and dim >= total:
                spec[i] = axes if len(axes) > 1 else axes[0]
                break
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(one, opt_tree)


def batch_spec(mesh: Mesh, global_batch: int, extra_dims: int = 1) -> P:
    """[B, ...]: batch on DP axes when divisible, replicated otherwise
    (long_500k has B=1 — the data axis idles and the roofline says so)."""
    axes = dp_axes(mesh)
    total = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
    if axes and global_batch % total == 0:
        return P(axes, *([None] * extra_dims))
    return P(*([None] * (extra_dims + 1)))


def batch_shardings(batch: Any, mesh: Mesh) -> Any:
    def one(leaf):
        nd = np.ndim(leaf)
        if nd == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, batch_spec(mesh, leaf.shape[0], nd - 1))
    return jax.tree.map(one, batch)


def cache_shardings(cache: Any, cfg: ModelConfig, mesh: Mesh) -> Any:
    """KV / SSM caches: [L, B, ...]: B on DP; KV caches shard the *sequence*
    dim on "model" (flash-decode style: per-shard partial attention + tiny
    softmax-stat/output psums — §Perf decode cells; sharding head_dim instead
    turned the score reduction into a per-layer GB-scale all-reduce)."""
    def one(path, leaf):
        ps = _path_str(path)
        nd = np.ndim(leaf)
        if nd == 0:
            return NamedSharding(mesh, P())
        shape = leaf.shape
        dp = batch_spec(mesh, shape[1], 0) if nd > 1 else P(None)
        dpax = dp[0] if len(dp) else None
        spec: list = [None] * nd
        spec[1] = dpax
        model_dim = None
        if re.search(r"(^|/)(k|v|shared_k|shared_v)$", ps):
            # [L, B, C, KV, dh]: prefer C (sequence); fall back to dh
            model_dim = 2 if shape[2] % mesh.shape["model"] == 0 else nd - 1
        elif ps.endswith("ssm"):
            model_dim = nd - 2          # P (head dim), N stays whole
        elif ps.endswith("conv"):
            model_dim = nd - 1          # channels
        if model_dim is not None and shape[model_dim] % mesh.shape["model"] == 0:
            spec[model_dim] = "model"
        return NamedSharding(mesh, P(*spec))
    return jax.tree_util.tree_map_with_path(one, cache)


def logits_sharding(mesh: Mesh, global_batch: int, cfg: ModelConfig,
                    with_seq: bool = True) -> NamedSharding:
    bspec = batch_spec(mesh, global_batch, 0)
    dpax = bspec[0] if len(bspec) else None
    vocab_ok = cfg.vocab % mesh.shape["model"] == 0
    dims = (dpax, None, "model" if vocab_ok else None) if with_seq \
        else (dpax, "model" if vocab_ok else None)
    return NamedSharding(mesh, P(*dims))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# serving slot grid (slot-axis rules; consumed by serving/adapt.make_chunk_fn)
# ---------------------------------------------------------------------------
#
# The SNN serving chunk step is per-slot separable — every per-stream
# quantity is a single slot-leading array (``StreamState`` leaves, the
# compact ``[S, L, J, T, bk, bo]`` delta tensor — or its dense
# ``[S, L, Kmax, N]`` baseline; ``slot_spec(0)`` is a rank-agnostic prefix
# so both share one rule — and the ``[S]`` adapt mask) or carries the slot
# axis second (the ``[C, S, n_in]`` event and ``[C, S]`` valid staging
# buffers). Sharding is therefore one rule applied twice: "slots" on the
# slot axis, everything else replicated. The frozen base params replicate —
# under the compact hot path that is the ``{"wc", "idx", "readout"}`` exec
# rep, read-only and small next to the delta grid.

SLOT_AXIS = "slots"


def slot_devices(mesh: Mesh) -> int:
    return mesh.shape[SLOT_AXIS]


def round_up_slots(n_slots: int, mesh: Mesh) -> int:
    """Smallest multiple of the mesh's slot-device count >= ``n_slots``."""
    d = slot_devices(mesh)
    return -(-n_slots // d) * d


def tier_slot_allocation(counts, mesh: Mesh) -> list:
    """Device-aware slot widths for a multi-tier grid: each tier's
    requested slot count is padded to a multiple of the slot-mesh size
    (every device owns an equal shard of every tier) and floored at two
    slots per device (below that XLA:CPU's gemv path changes the
    K-reduction order and costs bit-identity with 1-device) — the same
    rule the single-grid scheduler has always applied, per tier."""
    floor = 2 * slot_devices(mesh)
    return [max(round_up_slots(int(n), mesh), floor) for n in counts]


def check_slot_divisible(n_slots: int, mesh: Mesh) -> None:
    d = slot_devices(mesh)
    if n_slots % d != 0:
        raise ValueError(
            f"n_slots={n_slots} not divisible by the {d}-device slot mesh; "
            f"use round_up_slots ({round_up_slots(n_slots, mesh)})")


def slot_spec(slot_dim: int = 0) -> P:
    """Partition the ``slot_dim``-th axis over "slots", rest replicated."""
    return P(*((None,) * slot_dim), SLOT_AXIS)


def slot_sharding(mesh: Mesh, slot_dim: int = 0) -> NamedSharding:
    return NamedSharding(mesh, slot_spec(slot_dim))


def stream_shardings(tree: Any, mesh: Mesh) -> Any:
    """Slot-leading NamedShardings for StreamState / delta pytrees (every
    leaf has the slot axis first — the lane-surgery layout invariant)."""
    return jax.tree_util.tree_map(lambda _: slot_sharding(mesh), tree)


def chunk_step_specs(want_factors: bool = True) -> Tuple[Tuple, Tuple]:
    """shard_map specs for ``fn(params, deltas, state, events, valid,
    adapt_mask) -> (deltas, state, metrics)``.

    Pytree-prefix form: ``P()`` replicates the whole params tree, one
    slot-leading spec covers every StreamState leaf; ``ChunkMetrics`` needs
    per-field specs because ``logits``/``window_end`` carry the slot axis
    second. Zero collectives inside the step — each device advances only
    its slot shard.

    ``want_factors`` mirrors the static flag on ``make_chunk_fn``: when
    False the metrics carry no DSST factor leaves (``pre_mag``/``post_mag``
    are None) and the spec tree matches; when True each device's factors
    leave the shard-mapped step already reduced over its slot shard, one
    ``[1, L, ·]`` partial per device (``[D, L, ·]`` in all), which the
    jitted chunk fn combines — see ``chunk_step_shardings``.
    """
    from repro.core.snn import ChunkMetrics
    s0, s1 = slot_spec(0), slot_spec(1)
    fac = s0 if want_factors else None
    metrics = ChunkMetrics(
        logits=s1, window_end=s1, sop_forward=s0, sop_wu=s0,
        sop_wu_offered=s0, gate_opened=s0, gate_offered=s0,
        local_loss=s0, steps=s0, pre_mag=fac, post_mag=fac)
    in_specs = (P(), s0, s0, s1, s1, s0)
    out_specs = (s0, s0, metrics)
    return in_specs, out_specs


def chunk_step_shardings(mesh: Mesh,
                         want_factors: bool = True) -> Tuple[Tuple, Tuple]:
    """The chunk-fn jit's in/out NamedShardings.

    Mostly ``chunk_step_specs`` as shardings, with one deliberate
    difference: the jitted chunk fn combines the devices' ``[D, L, ·]``
    DSST-factor partials with the order-fixed ``engine.ordered_slot_sum``
    *after* the shard-mapped step, so by the time they are jit outputs
    they have no slot axis — they replicate (``P()``), ``[L, Kmax]`` /
    ``[L, N]`` and a few KB per grid step.
    """
    in_specs, out_specs = chunk_step_specs(want_factors)
    as_sh = lambda tree: jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p), tree)
    in_sh, out_sh = as_sh(in_specs), as_sh(out_specs)
    if want_factors:
        rep = replicated(mesh)
        out_sh = (out_sh[0], out_sh[1],
                  out_sh[2]._replace(pre_mag=rep, post_mag=rep))
    return in_sh, out_sh
