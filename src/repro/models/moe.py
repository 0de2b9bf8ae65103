"""Mixture-of-Experts layer (Mixtral / Moonlight families).

Token-choice top-k routing with **capacity-bounded scatter dispatch**: tokens
are ranked within their expert (sort + searchsorted, all static shapes) and
scattered into an ``[E, C, D]`` buffer — never the quadratic one-hot
``[tokens, E, C]`` einsum, which is unusable at 32k contexts. FLOPs scale
with ``top_k · capacity_factor``, matching the 6·N_active·D roofline
accounting; overflowing tokens are dropped (contribute 0), standard for
TPU MoE.

Sharding (launch/sharding.py):
* ``moe_shard_experts=False`` (Mixtral: 8 big experts) — TP *inside* each
  expert: ``w1 [E, D, F]`` sharded on F over "model"; dispatch buffer stays
  on the token shards (no all-to-all).
* ``moe_shard_experts=True`` (Moonlight: 64 small experts) — EP: experts
  sharded over "model"; the scatter/gather across expert shards lowers to
  all-to-all on the dispatch buffer (visible in the §Dry-run collective
  schedule).

The paper's techniques compose here: MoE is *itself* structured sparsity at
expert granularity; block-N:M DSST applies inside each expert's FFN (shared
kept-row pattern across experts in compact mode), and per-expert router load
is the natural IA statistic for gated updates.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SparsityConfig
from .layers import linear_init, _rows_from_umask
from repro.core.sparsity import NMSpec, random_unit_mask


def moe_init(rng, cfg: ModelConfig, dtype, sp: Optional[SparsityConfig] = None):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(rng, 5)
    sp_e = sp if (sp and "expert" in sp.targets) else None
    p: Dict[str, jax.Array] = {
        "router": jax.random.normal(ks[0], (d, e), dtype) * (d ** -0.5),
    }

    def expert_mat(rng, k_in, k_out, sp_):
        if sp_ is None or sp_.mode == "masked":
            w = jax.random.normal(rng, (e, k_in, k_out), dtype) * (k_in ** -0.5)
            if sp_ is None:
                return {"w": w}
            spec = NMSpec(n=sp_.n, m=sp_.m, block=sp_.block, out_tile=k_out)
            umask = random_unit_mask(jax.random.fold_in(rng, 7), spec, k_in, k_out)
            return {"w": w, "umask": umask}
        # compact: shared kept-row pattern across experts
        spec = NMSpec(n=sp_.n, m=sp_.m, block=sp_.block, out_tile=k_out)
        umask = random_unit_mask(jax.random.fold_in(rng, 7), spec, k_in, k_out)
        rows = _rows_from_umask(umask[:, 0], sp_.block, n=sp_.n, m=sp_.m)
        kc = k_in * sp_.n // sp_.m
        scale = (k_in * sp_.density) ** -0.5
        return {"w": jax.random.normal(rng, (e, kc, k_out), dtype) * scale, "rows": rows}

    p["w1"] = expert_mat(ks[1], d, f, sp_e)
    p["w2"] = expert_mat(ks[2], f, d, sp_e)
    if cfg.act == "swiglu":
        p["w3"] = expert_mat(ks[3], d, f, sp_e)
    return p


def _expert_apply(pm, x, sp: Optional[SparsityConfig]):
    """x [E, C, K] @ w [E, K', O] for any storage form."""
    if "rows" in pm:
        return jnp.einsum("eck,eko->eco", jnp.take(x, pm["rows"], axis=-1), pm["w"])
    if "umask" in pm:
        # STE as in layers.linear_apply (dense grads for DSST regrow)
        e, k, o = pm["w"].shape
        maskf = jnp.repeat(pm["umask"], k // pm["umask"].shape[-2], axis=-2)
        w = pm["w"]
        w_used = w - jax.lax.stop_gradient(w * (1.0 - maskf.astype(w.dtype)))
        return jnp.einsum("eck,eko->eco", x, w_used)
    return jnp.einsum("eck,eko->eco", x, pm["w"])


def capacity(n_tokens: int, cfg: ModelConfig) -> int:
    c = int(n_tokens * cfg.moe_top_k * cfg.moe_capacity_factor / cfg.moe_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8


def _dispatch(flat: jax.Array, router_w: jax.Array, cfg: ModelConfig, c: int):
    """Route flat [N, D] tokens: returns (slot [N*K], gate [N,K], aux pieces)."""
    n, d = flat.shape
    e, k = cfg.moe_experts, cfg.moe_top_k
    logits = flat @ router_w.astype(flat.dtype)               # [N, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate, eids = jax.lax.top_k(probs, k)                      # [N, K]
    gate = (gate / gate.sum(-1, keepdims=True)).astype(flat.dtype)

    # rank of each (token, choice) within its expert — sort-based, static shapes
    flat_e = eids.reshape(-1)                                 # [N*K]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(e), side="left")
    rank_sorted = jnp.arange(n * k) - starts[sorted_e]
    rank = jnp.zeros_like(rank_sorted).at[order].set(rank_sorted)
    slot = jnp.where(rank < c, flat_e * c + rank, e * c)      # overflow -> trash

    me = probs.mean(axis=0)
    ce_frac = jnp.zeros((e,)).at[flat_e].add(1.0) / (n * k)
    aux = {"moe_aux": (e * jnp.sum(me * ce_frac)).astype(jnp.float32),
           "moe_dropped": (rank >= c).mean().astype(jnp.float32),
           "moe_load": ce_frac}
    return slot, gate, aux


def _expert_ffn(p, ebuf: jax.Array, cfg: ModelConfig, sp) -> jax.Array:
    h = _expert_apply(p["w1"], ebuf, sp)
    if cfg.act == "swiglu":
        h = jax.nn.silu(h) * _expert_apply(p["w3"], ebuf, sp)
    elif cfg.act == "relu2":
        h = jnp.square(jax.nn.relu(h))
    else:
        h = jax.nn.gelu(h)
    return _expert_apply(p["w2"], h, sp)                      # [E, C, D]


def _combine(flat, eout, slot, gate, c):
    n, d = flat.shape
    e = eout.shape[0]
    k = gate.shape[1]
    flat_out = jnp.concatenate([eout.reshape(e * c, d),
                                jnp.zeros((1, d), flat.dtype)])
    routed = flat_out[slot].reshape(n, k, d)
    return (routed * gate[..., None]).sum(axis=1)


def moe_apply(p, x: jax.Array, cfg: ModelConfig,
              sp: Optional[SparsityConfig] = None) -> Tuple[jax.Array, Dict]:
    """x [B, S, D] -> (out [B, S, D], aux dict with load-balance loss/stats).

    Under an active SPMD context with ``shardmap_moe`` the dispatch runs
    inside shard_map so token scatter/gather stays LOCAL per data shard —
    the pjit partitioner otherwise replicates the dispatch buffer across
    shards (EXPERIMENTS.md §Perf, mixtral/moonshot cells)."""
    from repro.launch import spmd as spmd_lib
    ctx = spmd_lib.current()
    compact_experts = any("rows" in p[w] for w in ("w1", "w2") if w in p)
    if ctx is not None and ctx.shardmap_moe and not compact_experts:
        return _moe_apply_shardmap(p, x, cfg, sp, ctx)

    b, s, d = x.shape
    n = b * s
    c = capacity(n, cfg)
    flat = x.reshape(n, d)
    slot, gate, aux = _dispatch(flat, p["router"], cfg, c)
    buf = jnp.zeros((cfg.moe_experts * c + 1, d), x.dtype)
    buf = buf.at[slot].set(jnp.repeat(flat, cfg.moe_top_k, axis=0))
    ebuf = buf[: cfg.moe_experts * c].reshape(cfg.moe_experts, c, d)
    eout = _expert_ffn(p, ebuf, cfg, sp)
    out = _combine(flat, eout, slot, gate, c).reshape(b, s, d)
    return out, aux


def _moe_apply_shardmap(p, x, cfg: ModelConfig, sp, ctx) -> Tuple[jax.Array, Dict]:
    """MoE with data-shard-local dispatch (shard_map).

    * TP-inside-expert (mixtral): expert FFN hidden dim sharded on the TP
      axis; every shard builds the full local dispatch buffer, computes its
      F-slice, and one psum over TP finishes the down-projection — the same
      all-reduce a dense Megatron MLP pays. No token buffers ever cross the
      data axis.
    * EP (moonshot): experts sharded on TP; each shard scatters its local
      tokens into the full [E, C, D] buffer, computes only its E/TP expert
      slice, and the combined output psums over TP (non-local experts
      contribute zeros). Comm = one [n_local, D] all-reduce instead of the
      partitioner's buffer replication.
    """
    from jax.sharding import PartitionSpec as P

    mesh, tp = ctx.mesh, ctx.tp_axis
    b, s, d = x.shape
    dp_n = 1
    for a in ctx.dp_axes:
        dp_n *= mesh.shape[a]
    dp = ctx.dp_axes if (b % dp_n == 0 and dp_n > 1) else None
    xspec = P(dp, None, None)
    tp_n = mesh.shape[tp]
    e = cfg.moe_experts
    n_loc = (b // dp_n if dp else b) * s
    c = capacity(n_loc, cfg)

    ep = cfg.moe_shard_experts
    if ep:
        wspec = {"w1": {"w": P(tp, None, None)}, "w2": {"w": P(tp, None, None)}}
        if "w3" in p:
            wspec["w3"] = {"w": P(tp, None, None)}
        e_loc = e // tp_n
    else:
        wspec = {"w1": {"w": P(None, None, tp)}, "w2": {"w": P(None, tp, None)}}
        if "w3" in p:
            wspec["w3"] = {"w": P(None, None, tp)}
    in_specs = (xspec, P(None, None), wspec)
    out_specs = (xspec, {"moe_aux": P(), "moe_dropped": P(), "moe_load": P()})

    def body(xl, router, wl):
        nl = xl.shape[0] * xl.shape[1]
        flat = xl.reshape(nl, d)
        slot, gate, aux = _dispatch(flat, router, cfg, c)
        buf = jnp.zeros((e * c + 1, d), xl.dtype)
        buf = buf.at[slot].set(jnp.repeat(flat, cfg.moe_top_k, axis=0))
        ebuf = buf[: e * c].reshape(e, c, d)
        if ep:
            shard = jax.lax.axis_index(tp)
            ebuf_loc = jax.lax.dynamic_slice_in_dim(ebuf, shard * e_loc, e_loc, 0)
            eout_loc = _expert_ffn(wl, ebuf_loc, cfg, sp)      # [E/tp, C, D]
            eout = jnp.zeros((e, c, d), xl.dtype)
            eout = jax.lax.dynamic_update_slice_in_dim(
                eout, eout_loc.astype(xl.dtype), shard * e_loc, 0)
            out = _combine(flat, eout, slot, gate, c)
            out = jax.lax.psum(out, tp)                        # sum expert shards
        else:
            eout = _expert_ffn(wl, ebuf, cfg, sp)              # partial over F
            out = jax.lax.psum(_combine(flat, eout, slot, gate, c), tp)
        if dp:
            aux = {k: (jax.lax.pmean(v, dp) if v.ndim == 0 else
                       jax.lax.pmean(v, dp)) for k, v in aux.items()}
        return out.reshape(xl.shape), aux

    wl = {k: p[k] for k in ("w1", "w2", "w3") if k in p}
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out, aux = fn(x, p["router"], wl)
    return out, aux
