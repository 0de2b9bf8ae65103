"""Primitive layers: norms, rotary embeddings, GQA/SWA attention, MLPs,
and the (optionally block-N:M sparse) linear projection.

Sparse linear parameter forms (configs.SparsityConfig.mode):

* dense    : {"w": [K, O]}
* masked   : {"w": [K, O], "umask": bool [K/block, 1]} — dense storage,
             pattern applied at use; CPU-friendly, used by training tests.
* compact  : {"w": [Kc, O], "rows": int32 [Kc]} — only kept rows stored
             (Kc = K·n/m); forward is gather + dense matmul. This is the
             paper's weight-memory cut, and what the dry-run/roofline sees.
             The pattern is shared across output columns (J=1 — the
             coarsest point on the paper's mask-diversity/efficiency
             trade-off, Fig. 5 middle); per-out-tile diversity lives in the
             SNN's compact layout (kernels/nm_spmm).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, SparsityConfig
from repro.core.sparsity import NMSpec, expand_unit_mask, random_unit_mask


# ---------------------------------------------------------------------------
# (sparse) linear
# ---------------------------------------------------------------------------

def linear_init(rng, k: int, o: int, dtype, sp: Optional[SparsityConfig] = None,
                scale: Optional[float] = None):
    scale = (k ** -0.5) if scale is None else scale
    if sp is not None and (k % sp.block or (k // sp.block) % sp.m):
        # input dim doesn't tile into N:M groups (e.g. deepseek w2 with
        # d_ff=22016 -> 172 blocks % m) — stay dense rather than mis-mask.
        sp = None
    if sp is None:
        return {"w": jax.random.normal(rng, (k, o), dtype) * scale}
    r1, r2 = jax.random.split(rng)
    spec = NMSpec(n=sp.n, m=sp.m, block=sp.block, out_tile=o)
    umask = random_unit_mask(r1, spec, k, o)                      # [KB, 1]
    scale = scale / (sp.density ** 0.5)                           # variance-preserving
    if sp.mode == "masked":
        w = jax.random.normal(r2, (k, o), dtype) * scale
        return {"w": w, "umask": umask}
    kc = k * sp.n // sp.m
    rows = _rows_from_umask(umask[:, 0], sp.block, n=sp.n, m=sp.m)
    w = jax.random.normal(r2, (kc, o), dtype) * scale
    return {"w": w, "rows": rows}


def _rows_from_umask(block_mask: jax.Array, block: int, *, n: int, m: int) -> jax.Array:
    """bool [KB] -> int32 [KB·n/m·block] kept dense-row indices (sorted).

    The kept count is static by construction (exactly n per group of m), so
    this traces under vmap/eval_shape — no data-dependent shapes."""
    kb = block_mask.shape[0]
    t = kb * n // m
    blocks = jnp.sort(jnp.argsort(~block_mask, stable=True)[:t])  # kept block ids
    return (blocks[:, None] * block + jnp.arange(block)[None, :]).reshape(-1).astype(jnp.int32)


def linear_apply(p: Dict[str, jax.Array], x: jax.Array, sp: Optional[SparsityConfig] = None):
    """x [..., K] @ W -> [..., O] for any storage form."""
    if "rows" in p:
        return jnp.take(x, p["rows"], axis=-1) @ p["w"]
    if "umask" in p:
        # straight-through estimator: forward sees w·mask, backward sees a
        # DENSE gradient — exactly what DSST's regrow scoring needs (RigL).
        # The optimizer re-masks updates (optim/sparse.build_update_scale).
        maskf = jnp.repeat(p["umask"], _block_rows(p), axis=-2).astype(p["w"].dtype)
        w = p["w"]
        w_used = w - jax.lax.stop_gradient(w * (1.0 - maskf))
        return x @ w_used
    return x @ p["w"]


def _block_rows(p) -> int:
    """Rows per mask unit: K / KB (umask is [KB, 1], w is [K, O])."""
    return p["w"].shape[-2] // p["umask"].shape[-2]


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(d: int, dtype) -> jax.Array:
    return jnp.ones((d,), dtype)


def rmsnorm(g: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * g


# ---------------------------------------------------------------------------
# rotary position embeddings (RoPE and Qwen2-VL's M-RoPE)
# ---------------------------------------------------------------------------

def _inv_freq(d_half: int, theta: float, dtype=jnp.float32) -> jax.Array:
    return theta ** (-jnp.arange(0, d_half, dtype=dtype) / d_half)


def rope_angles(pos: jax.Array, d_head: int, theta: float) -> jax.Array:
    """pos [B, S] -> angles [B, S, d_head//2]."""
    return pos[..., None].astype(jnp.float32) * _inv_freq(d_head // 2, theta)


def mrope_angles(pos3: jax.Array, d_head: int, theta: float,
                 sections: Tuple[int, int, int]) -> jax.Array:
    """Multi-axis RoPE: pos3 [3, B, S] (temporal, height, width).

    Frequency slot i takes its position from the section it falls in —
    Qwen2-VL's M-RoPE with the text-degenerate case pos3[0]==pos3[1]==pos3[2].
    """
    d_half = d_head // 2
    assert sum(sections) == d_half, (sections, d_half)
    sec_id = jnp.repeat(jnp.arange(3), jnp.array(sections), total_repeat_length=d_half)
    pos_per_freq = pos3[sec_id]                                   # [d_half, B, S]
    pos_per_freq = jnp.moveaxis(pos_per_freq, 0, -1)              # [B, S, d_half]
    return pos_per_freq.astype(jnp.float32) * _inv_freq(d_half, theta)


def apply_rotary(x: jax.Array, angles: jax.Array) -> jax.Array:
    """x [B, S, H, dh], angles [B, S, dh//2] — rotate-half convention."""
    d_half = x.shape[-1] // 2
    x1, x2 = x[..., :d_half], x[..., d_half:]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


# ---------------------------------------------------------------------------
# attention (GQA, optional sliding window, full + cached decode paths)
# ---------------------------------------------------------------------------

def attn_init(rng, cfg: ModelConfig, dtype, sp: Optional[SparsityConfig] = None):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    sp_attn = sp if (sp and "attn" in sp.targets) else None
    return {
        "wq": linear_init(ks[0], d, h * dh, dtype, sp_attn),
        "wk": linear_init(ks[1], d, kv * dh, dtype, sp_attn),
        "wv": linear_init(ks[2], d, kv * dh, dtype, sp_attn),
        "wo": linear_init(ks[3], h * dh, d, dtype, sp_attn),
    }


def _gqa_scores(q, k):
    """q [B,S,H,dh], k [B,T,KV,dh] -> [B, KV, H/KV, S, T]."""
    b, s, h, dh = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, dh)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k) / (dh ** 0.5)


def _gqa_out(probs, v):
    """probs [B,KV,G,S,T], v [B,T,KV,dh] -> [B,S,H,dh]."""
    b, kvh, g, s, t = probs.shape
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(b, s, kvh * g, -1)


def causal_mask(s: int, window: Optional[int] = None, dtype=jnp.float32) -> jax.Array:
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    ok = j <= i
    if window is not None:
        ok &= (i - j) < window
    return jnp.where(ok, 0.0, -jnp.inf).astype(dtype)


def attn_full(p, x, angles, cfg: ModelConfig, sp=None):
    """Training / prefill attention over the whole sequence."""
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear_apply(p["wq"], x, sp).reshape(b, s, h, dh)
    k = linear_apply(p["wk"], x, sp).reshape(b, s, kv, dh)
    v = linear_apply(p["wv"], x, sp).reshape(b, s, kv, dh)
    if angles is not None:
        q, k = apply_rotary(q, angles), apply_rotary(k, angles)
    scores = _gqa_scores(q, k)
    scores = scores + causal_mask(s, cfg.swa_window, scores.dtype)[None, None, None]
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = _gqa_out(probs, v).reshape(b, s, h * dh)
    return linear_apply(p["wo"], out, sp), (k, v)


def attn_full_chunked(p, x, angles, cfg: ModelConfig, sp=None, q_chunk: int = 512,
                      unroll: bool = False):
    """Query-chunked causal attention — O(q_chunk · S) live memory.

    A ``lax.scan`` over query chunks keeps the [qc, S] score slab (not the
    full [S, S] one) live; with per-layer remat this bounds attention memory
    at 32k+ contexts. Keys/values stay whole (they are KV-head-sharded on the
    mesh); causal/SWA masking is reconstructed from absolute positions.
    """
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qc = min(q_chunk, s)
    assert s % qc == 0, (s, qc)
    q = linear_apply(p["wq"], x, sp).reshape(b, s, h, dh)
    k = linear_apply(p["wk"], x, sp).reshape(b, s, kv, dh)
    v = linear_apply(p["wv"], x, sp).reshape(b, s, kv, dh)
    if angles is not None:
        q, k = apply_rotary(q, angles), apply_rotary(k, angles)

    nq = s // qc
    qs = jnp.moveaxis(q.reshape(b, nq, qc, h, dh), 1, 0)        # [nq, B, qc, H, dh]
    j_abs = jnp.arange(s)

    def chunk_fn(_, inp):
        qi, ci = inp
        i_abs = ci * qc + jnp.arange(qc)
        ok = j_abs[None, :] <= i_abs[:, None]
        if cfg.swa_window is not None:
            ok &= (i_abs[:, None] - j_abs[None, :]) < cfg.swa_window
        scores = _gqa_scores(qi, k)                             # [B,KV,G,qc,S]
        scores = jnp.where(ok[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores.astype(jnp.float32), -1).astype(x.dtype)
        return None, _gqa_out(probs, v)                         # [B,qc,H,dh]

    # unroll=True: cost-probe mode — XLA's cost_analysis counts a while-loop
    # body once, so flop-accounting probes inline the chunk loop.
    _, outs = jax.lax.scan(chunk_fn, None, (qs, jnp.arange(nq)), unroll=unroll)
    out = jnp.moveaxis(outs, 0, 1).reshape(b, s, h * dh)
    return linear_apply(p["wo"], out, sp), (k, v)


def attn_full_flash(p, x, angles, cfg: ModelConfig, sp=None,
                    interpret: bool = False, force_pallas: bool = False):
    """Training/prefill attention through the flash Pallas kernel
    (kernels/flash_attn): O(S·d) HBM traffic instead of the score path.
    TPU runtime path; interpret mode for CPU validation."""
    from repro.kernels.flash_attn.ops import flash_attention
    b, s, d = x.shape
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = linear_apply(p["wq"], x, sp).reshape(b, s, h, dh)
    k = linear_apply(p["wk"], x, sp).reshape(b, s, kv, dh)
    v = linear_apply(p["wv"], x, sp).reshape(b, s, kv, dh)
    if angles is not None:
        q, k = apply_rotary(q, angles), apply_rotary(k, angles)
    out = flash_attention(q, k, v, cfg.swa_window, interpret, force_pallas)
    out = out.reshape(b, s, h * dh)
    return linear_apply(p["wo"], out, sp), (k, v)


def attn_decode(p, x, angles, cache_k, cache_v, pos, cfg: ModelConfig, sp=None):
    """One-token decode against a (possibly ring-buffered SWA) KV cache.

    ``cache_k/v``: [B, C, KV, dh] with C = min(max_seq, swa_window or inf);
    ``pos``: scalar int32 — tokens already in the cache.
    Returns (out [B,1,D], new_k, new_v).
    """
    b, s, d = x.shape
    assert s == 1
    h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    c = cache_k.shape[1]
    q = linear_apply(p["wq"], x, sp).reshape(b, 1, h, dh)
    k = linear_apply(p["wk"], x, sp).reshape(b, 1, kv, dh)
    v = linear_apply(p["wv"], x, sp).reshape(b, 1, kv, dh)
    if angles is not None:
        q, k = apply_rotary(q, angles), apply_rotary(k, angles)

    slot = pos % c                                   # ring write (SWA) / linear (full)
    cache_k = jax.lax.dynamic_update_slice(cache_k, k, (0, slot, 0, 0))
    cache_v = jax.lax.dynamic_update_slice(cache_v, v, (0, slot, 0, 0))

    scores = _gqa_scores(q, cache_k)                 # [B,KV,G,1,C]
    slot_ids = jnp.arange(c)
    # absolute position each slot currently holds
    abs_pos = jnp.where(slot_ids <= slot, pos - slot + slot_ids,
                        pos - slot + slot_ids - c)
    valid = (abs_pos >= 0) & (abs_pos <= pos)
    if cfg.swa_window is not None:
        valid &= (pos - abs_pos) < cfg.swa_window
    scores = jnp.where(valid[None, None, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    out = _gqa_out(probs, cache_v).reshape(b, 1, h * dh)
    return linear_apply(p["wo"], out, sp), cache_k, cache_v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(rng, cfg: ModelConfig, dtype, sp: Optional[SparsityConfig] = None,
             d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    sp_mlp = sp if (sp and "mlp" in sp.targets) else None
    ks = jax.random.split(rng, 3)
    p = {"w1": linear_init(ks[0], d, f, dtype, sp_mlp),
         "w2": linear_init(ks[1], f, d, dtype, sp_mlp)}
    if cfg.act == "swiglu":
        p["w3"] = linear_init(ks[2], d, f, dtype, sp_mlp)
    return p


def mlp_apply(p, x, cfg: ModelConfig, sp: Optional[SparsityConfig] = None):
    sp_mlp = sp if (sp and "mlp" in sp.targets) else None
    h = linear_apply(p["w1"], x, sp_mlp)
    if cfg.act == "swiglu":
        h = jax.nn.silu(h) * linear_apply(p["w3"], x, sp_mlp)
    elif cfg.act == "relu2":                       # Nemotron-4 squared ReLU
        h = jnp.square(jax.nn.relu(h))
    elif cfg.act == "gelu":
        h = jax.nn.gelu(h)
    else:
        raise ValueError(cfg.act)
    return linear_apply(p["w2"], h, sp_mlp)


# ---------------------------------------------------------------------------
# embeddings / head
# ---------------------------------------------------------------------------

def embed_init(rng, cfg: ModelConfig, dtype):
    p = {"tok": jax.random.normal(rng, (cfg.vocab, cfg.d_model), dtype) * 0.02}
    if cfg.frontend:
        r2 = jax.random.fold_in(rng, 1)
        p["frontend_proj"] = jax.random.normal(
            r2, (cfg.frontend_dim, cfg.d_model), dtype) * (cfg.frontend_dim ** -0.5)
    return p


def embed_apply(p, tokens=None, embeds=None):
    if embeds is not None:
        return embeds @ p["frontend_proj"]
    return jnp.take(p["tok"], tokens, axis=0)
