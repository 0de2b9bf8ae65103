"""Compact N:M ops (nm_spmm, nm_spmm_deltas, wu_outer_slots) against dense
references, and the flash-attention kernel (Pallas interpret=True) vs its
oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparsity as sp
from repro.kernels.nm_spmm import ops as nm_ops, ref as nm_ref
from repro.kernels.wu_outer import ref as wu_ref


def _mk_sparse(seed, k, o, bk, bo, n, m, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    spec = sp.NMSpec(n=n, m=m, block=bk, out_tile=bo)
    mask = sp.random_unit_mask(ks[0], spec, k, o)
    w = jax.random.normal(ks[1], (k, o)).astype(dtype)
    wc, idx = nm_ops.make_compact(w, mask, bk, bo)
    x = jax.random.normal(ks[2], (16, k)).astype(dtype)
    return x, w, wc, idx, mask, spec


NM_CASES = [
    # (k, o, bk, bo, n, m)
    (32, 16, 4, 8, 2, 4),
    (64, 32, 8, 16, 1, 2),
    (128, 128, 16, 32, 2, 8),
    (48, 24, 4, 8, 3, 4),
]


@pytest.mark.parametrize("k,o,bk,bo,n,m", NM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nm_spmm_vs_dense(k, o, bk, bo, n, m, dtype):
    x, w, wc, idx, mask, spec = _mk_sparse(0, k, o, bk, bo, n, m, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    y_r = nm_ref.nm_spmm(x, wc, idx)
    y_d = nm_ref.nm_spmm_dense_ref(x, wc, idx)
    y_m = x @ sp.apply_mask(w, mask, spec)
    np.testing.assert_allclose(np.asarray(y_r, np.float32), np.asarray(y_d, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(y_r, np.float32), np.asarray(y_m, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("k,o,bk,bo,n,m", NM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nm_spmm_deltas_vs_dense(k, o, bk, bo, n, m, dtype):
    """Per-slot compact deltas sharing one idx: slot s contracts with its
    own densified delta, never a neighbour's."""
    x, _, wc, idx, _, _ = _mk_sparse(1, k, o, bk, bo, n, m, dtype)
    S = x.shape[0]
    delta = jax.random.normal(jax.random.PRNGKey(9),
                              (S,) + wc.shape).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    got = nm_ref.nm_spmm_deltas(x, delta, idx)
    dense = jnp.stack([nm_ref.densify(delta[s], idx, k) for s in range(S)])
    want = jnp.einsum("sk,skn->sn", x, dense)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_nm_spmm_flop_scaling():
    """Work scales with n/m: the compact layout only visits kept blocks."""
    _, _, wc, idx, _, _ = _mk_sparse(0, 128, 128, 16, 32, 2, 8, jnp.float32)
    assert wc.shape[1] == idx.shape[1] == 2 * (128 // 16 // 8)   # G*n kept blocks
    assert wc.size == 128 * 128 * 2 // 8                         # density × dense


def _kept_idx(seed, k, o, bk, bo):
    spec = sp.NMSpec(n=1, m=2, block=bk, out_tile=bo)
    mask = sp.random_unit_mask(jax.random.PRNGKey(seed), spec, k, o)
    _, idx = nm_ops.make_compact(jnp.zeros((k, o)), mask, bk, bo)
    dm = sp.expand_unit_mask(mask, spec, k, o).astype(jnp.float32)
    return idx, dm


@pytest.mark.parametrize("s,k,o,bk,bo", [(8, 32, 16, 4, 8),
                                         (16, 64, 32, 8, 16),
                                         (4, 16, 8, 4, 8)])
def test_wu_outer_sweep(s, k, o, bk, bo):
    """Each slot's compact update equals its own dense outer product at the
    kept coordinates; a slot whose scale is 0 gets exactly nothing."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    idx, dm = _kept_idx(0, k, o, bk, bo)
    pre = jax.random.normal(ks[0], (s, k))
    mod = jax.random.normal(ks[1], (s, o))
    scale = jax.random.uniform(ks[2], (s,), minval=0.01, maxval=0.1)
    scale = scale.at[::3].set(0.0)
    got = wu_ref.wu_outer_slots(pre, mod, idx, scale, bk, bo)
    dense = jnp.stack([nm_ref.densify(got[i], idx, k) for i in range(s)])
    want = scale[:, None, None] * pre[:, :, None] * mod[:, None, :] * dm
    np.testing.assert_allclose(dense, want, atol=1e-6)
    assert float(jnp.abs(got[::3]).max()) == 0.0


def test_wu_outer_gate_zero_is_noop():
    """A gated-off layer's WU is exactly zero (the skip the chip doesn't pay for)."""
    idx, _ = _kept_idx(0, 16, 8, 4, 8)
    pre = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    mod = jax.random.normal(jax.random.PRNGKey(2), (8, 8))
    out = wu_ref.wu_outer_slots(pre, mod, idx, jnp.zeros((8,)), 4, 8)
    assert float(jnp.abs(out).max()) == 0.0


# ---------------------------------------------------------------------------
# flash attention (kernels/flash_attn): fwd + bwd vs ref, causal + SWA + GQA
# ---------------------------------------------------------------------------
from repro.kernels.flash_attn import ops as fa_ops, ref as fa_ref
from repro.kernels.flash_attn.kernel import flash_fwd


@pytest.mark.parametrize("b,s,h,kv,dh,bq,bk", [
    (2, 32, 4, 2, 16, 8, 8),
    (1, 64, 2, 2, 32, 16, 16),
    (2, 16, 4, 1, 8, 16, 16),   # single kv head (MQA), one tile
])
@pytest.mark.parametrize("window", [None, 8])
def test_flash_attention_fwd_sweep(b, s, h, kv, dh, bq, bk, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    want = fa_ref.attention(q, k, v, window)
    qk, kk, vk = fa_ops._to_kernel_layout(q, k, v)
    o, lse = flash_fwd(qk, kk, vk, bq=bq, bk=bk, window=window, interpret=True)
    got = fa_ops._from_kernel_layout(o, b, s, h, dh)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert bool(jnp.isfinite(lse).all())


@pytest.mark.parametrize("window", [None, 8])
def test_flash_attention_bwd_matches_autodiff(window):
    b, s, h, kv, dh = 2, 32, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (b, s, h, dh))
    k = jax.random.normal(ks[1], (b, s, kv, dh))
    v = jax.random.normal(ks[2], (b, s, kv, dh))
    dout = jax.random.normal(ks[3], (b, s, h, dh))
    g_ref = jax.grad(lambda *a: (fa_ref.attention(*a, window) * dout).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: (fa_ops.flash_attention(*a, window, True, True)
                                * dout).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, c in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, c, atol=5e-5, rtol=5e-5)


def test_flash_attention_model_path():
    """attn_full_flash == attn_full on the model layout."""
    import repro.configs as C
    from repro.models import layers as L
    cfg = C.get_reduced("phi3_medium_14b")
    p = L.attn_init(jax.random.PRNGKey(0), cfg, jnp.float32, None)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    ang = L.rope_angles(pos, cfg.head_dim, cfg.rope_theta)
    want, _ = L.attn_full(p, x, ang, cfg)
    got, _ = L.attn_full_flash(p, x, ang, cfg, interpret=True, force_pallas=True)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_flash_hbm_traffic_model():
    """BlockSpec-exact traffic is far below the unfused score path and scales
    ~linearly in S for fixed tiles (per q-tile k/v re-reads)."""
    from repro.kernels.flash_attn.ops import hbm_bytes, xla_score_path_bytes
    fl = hbm_bytes(16, 4096, 4, 128)
    xla = xla_score_path_bytes(16, 4096, 4, 128)
    assert fl < xla / 5
    assert hbm_bytes(16, 8192, 4, 128) < 5 * hbm_bytes(16, 4096, 4, 128)
