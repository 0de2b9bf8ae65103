"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sparsity as sp
from repro.kernels.nm_spmm import ops as nm_ops, ref as nm_ref
from repro.kernels.nm_spmm.kernel import nm_spmm_pallas
from repro.kernels.lif import ops as lif_ops, ref as lif_ref
from repro.kernels.lif.kernel import lif_pallas
from repro.kernels.wu_outer import ref as wu_ref
from repro.kernels.wu_outer.kernel import wu_outer_pallas


def _mk_sparse(seed, k, o, bk, bo, n, m, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    spec = sp.NMSpec(n=n, m=m, block=bk, out_tile=bo)
    mask = sp.random_unit_mask(ks[0], spec, k, o)
    w = jax.random.normal(ks[1], (k, o)).astype(dtype)
    wc, idx = nm_ops.make_compact(w, mask, bk, bo)
    x = jax.random.normal(ks[2], (16, k)).astype(dtype)
    return x, w, wc, idx, mask, spec


NM_CASES = [
    # (k, o, bk, bo, n, m, bm)
    (32, 16, 4, 8, 2, 4, 8),
    (64, 32, 8, 16, 1, 2, 16),
    (128, 128, 16, 32, 2, 8, 8),
    (48, 24, 4, 8, 3, 4, 4),
]


@pytest.mark.parametrize("k,o,bk,bo,n,m,bm", NM_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_nm_spmm_kernel_vs_refs(k, o, bk, bo, n, m, bm, dtype):
    x, w, wc, idx, mask, spec = _mk_sparse(0, k, o, bk, bo, n, m, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    y_k = nm_spmm_pallas(x, wc, idx, bm=bm, interpret=True)
    y_r = nm_ref.nm_spmm(x, wc, idx)
    y_d = nm_ref.nm_spmm_dense_ref(x, wc, idx)
    y_m = x @ sp.apply_mask(w, mask, spec)
    np.testing.assert_allclose(np.asarray(y_k, np.float32), np.asarray(y_r, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(y_r, np.float32), np.asarray(y_d, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(y_r, np.float32), np.asarray(y_m, np.float32),
                               atol=tol, rtol=tol)


def test_nm_spmm_custom_vjp_matches_dense_autodiff():
    x, w, wc, idx, mask, spec = _mk_sparse(1, 64, 32, 8, 16, 1, 2, jnp.float32)
    dy = jax.random.normal(jax.random.PRNGKey(7), (16, 32))

    f = lambda x_, wc_: (nm_ops.nm_spmm(x_, wc_, idx, True) * dy).sum()
    gx, gwc = jax.grad(f, argnums=(0, 1))(x, wc)
    fd = lambda x_, wd_: ((x_ @ wd_) * dy).sum()
    gxd, gwd = jax.grad(fd, argnums=(0, 1))(x, nm_ref.densify(wc, idx, 64))
    gwd_c, _ = nm_ops.make_compact(gwd, mask, 8, 16)
    np.testing.assert_allclose(gx, gxd, atol=1e-5)
    np.testing.assert_allclose(gwc, gwd_c, atol=1e-5)


def test_nm_spmm_flop_scaling():
    """Kernel work scales with n/m: the compact layout only visits kept blocks."""
    _, _, wc, idx, _, _ = _mk_sparse(0, 128, 128, 16, 32, 2, 8, jnp.float32)
    assert wc.shape[1] == idx.shape[1] == 2 * (128 // 16 // 8)   # G*n kept blocks
    assert wc.size == 128 * 128 * 2 // 8                         # density × dense


@pytest.mark.parametrize("shape", [(8, 128), (16, 256), (8, 250), (5, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lif_kernel_sweep(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    v = jax.random.normal(ks[0], shape).astype(dtype)
    tr = jax.random.uniform(ks[1], shape).astype(dtype)
    cur = jax.random.normal(ks[2], shape).astype(dtype)
    kw = dict(alpha=0.9, beta=0.85, theta=1.0)
    got = lif_ops.lif_step(v, tr, cur, interpret=True, **kw)
    want = lif_ref.lif_step(v, tr, cur, **kw)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(w, np.float32), atol=1e-4)


def test_lif_kernel_direct_tiles():
    v = jax.random.normal(jax.random.PRNGKey(0), (16, 256))
    tr = jnp.zeros((16, 256))
    cur = jax.random.normal(jax.random.PRNGKey(1), (16, 256))
    a = lif_pallas(v, tr, cur, alpha=0.5, beta=0.9, theta=0.7, bm=8, bn=128,
                   interpret=True)
    b = lif_ref.lif_step(v, tr, cur, alpha=0.5, beta=0.9, theta=0.7)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, atol=1e-5)


@pytest.mark.parametrize("b,k,o,bk,bo,bb", [(8, 32, 16, 4, 8, 4),
                                            (16, 64, 32, 8, 16, 8),
                                            (4, 16, 8, 4, 8, 4)])
def test_wu_outer_sweep(b, k, o, bk, bo, bb):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    spec = sp.NMSpec(n=1, m=2, block=bk, out_tile=bo)
    mask = sp.random_unit_mask(ks[0], spec, k, o)
    _, idx = nm_ops.make_compact(jnp.zeros((k, o)), mask, bk, bo)
    pre = jax.random.normal(ks[1], (b, k))
    mod = jax.random.normal(ks[2], (b, o))
    scale = jnp.float32(0.05)
    got = wu_outer_pallas(pre, mod, idx, scale, bk=bk, bo=bo, bb=bb, interpret=True)
    want = wu_ref.wu_outer(pre, mod, idx, scale, bk, bo)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_wu_outer_gate_zero_is_noop():
    """A gated-off layer's WU is exactly zero (the skip the chip doesn't pay for)."""
    spec = sp.NMSpec(n=1, m=2, block=4, out_tile=8)
    mask = sp.random_unit_mask(jax.random.PRNGKey(0), spec, 16, 8)
    _, idx = nm_ops.make_compact(jnp.zeros((16, 8)), mask, 4, 8)
    pre = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    mod = jax.random.normal(jax.random.PRNGKey(2), (8, 8))
    out = wu_ref.wu_outer(pre, mod, idx, jnp.float32(0.0), 4, 8)
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
