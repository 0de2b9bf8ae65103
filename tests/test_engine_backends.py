"""ref ↔ Pallas parity across the engine's backend seam.

``backend="pallas-interpret"`` forces every seam op (forward current, fused
LIF step, WU outer product) through the Pallas kernels in emulation mode, so
these run on CPU CI. Covered at two levels: each seam op in isolation on
masked N:M weights, and the full train/serve trajectories end-to-end.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.snn import (SNNConfig, init_params, init_state,
                            init_stream_deltas, init_stream_state, run_chunk,
                            run_sample)

CFG = SNNConfig(n_in=16, n_hidden=16, n_layers=2, n_out=4, t_steps=6)
REF = engine.make_backend(CFG)
PAL = engine.make_backend(dataclasses.replace(CFG, backend="pallas-interpret"))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _wreps(params):
    w, m = params["hidden"]["w"], params["hidden"]["mask"]
    return (engine.prepare_weights(w, m, CFG, REF),
            engine.prepare_weights(w, m, CFG, PAL))


def _slice(wrep, l):
    return jax.tree_util.tree_map(lambda a: a[l], wrep)


def test_forward_current_parity(params):
    wr, wp = _wreps(params)
    pre = jax.random.normal(jax.random.PRNGKey(1), (5, CFG.n_in))
    for l in range(CFG.n_layers):
        want = engine.fwd_current(REF, pre, _slice(wr, l), None)
        got = engine.fwd_current(PAL, pre, _slice(wp, l), None)
        # both must equal the dense masked matmul
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(want), np.asarray(pre @ params["hidden"]["w"][l]),
            atol=1e-5)


def test_lif_step_parity():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    v = jax.random.normal(ks[0], (5, CFG.n_hidden))
    tr = jax.random.uniform(ks[1], (5, CFG.n_hidden))
    cur = jax.random.normal(ks[2], (5, CFG.n_hidden))
    want = engine.lif(REF, CFG, v, tr, cur)
    got = engine.lif(PAL, CFG, v, tr, cur)
    for a, b in zip(want, got):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-5)


def test_wu_outer_parity(params):
    """The training WU on masked N:M weights: dense dw·mask (ref) equals the
    compact-layout outer product (wu_outer kernel), densified. The sparsity
    pattern rides inside the weight rep itself (mask_f for ref, kept block
    ids for compact) — train_wu takes no separate mask argument."""
    wr, wp = _wreps(params)
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    pre_tr = jax.random.uniform(ks[0], (5, CFG.n_in))
    mod = jax.random.normal(ks[1], (5, CFG.n_hidden))
    scale = jnp.float32(0.03)
    for l in range(CFG.n_layers):
        want = engine.train_wu(REF, CFG, _slice(wr, l), pre_tr, mod,
                               scale)["w"]
        got_rep = engine.train_wu(PAL, CFG, _slice(wp, l), pre_tr, mod,
                                  scale)
        got = engine.finalize_weights(
            jax.tree_util.tree_map(lambda a: a[None], got_rep), CFG, PAL)[0]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        # gate closed (scale 0) -> exactly no update on either path
        same = engine.train_wu(REF, CFG, _slice(wr, l), pre_tr, mod,
                               jnp.float32(0.0))["w"]
        np.testing.assert_array_equal(np.asarray(same),
                                      np.asarray(params["hidden"]["w"][l]))


def test_run_sample_backend_parity(params):
    st = init_state(CFG, 4)
    ev = jnp.asarray((np.random.default_rng(0).random(
        (CFG.t_steps, 4, CFG.n_in)) < 0.3).astype(np.float32))
    lab = jnp.asarray(np.arange(4) % CFG.n_out)
    outs = {}
    for backend in ("ref", "pallas-interpret"):
        cfg = dataclasses.replace(CFG, backend=backend)
        p2, _, m = run_sample(params, st, ev, lab, cfg, learn=True)
        outs[backend] = (np.asarray(m.logits), np.asarray(p2["hidden"]["w"]),
                         float(m.sop_wu))
    for a, b in zip(outs["ref"], outs["pallas-interpret"]):
        np.testing.assert_allclose(b, a, atol=1e-5)


def test_run_chunk_backend_parity(params):
    ss, dl = init_stream_state(CFG, 2), init_stream_deltas(CFG, 2)
    ev = jnp.asarray((np.random.default_rng(1).random(
        (6, 2, CFG.n_in)) < 0.3).astype(np.float32))
    valid = jnp.ones((6, 2), bool).at[4:, 1].set(False)
    outs = {}
    for backend in ("ref", "pallas-interpret"):
        cfg = dataclasses.replace(CFG, backend=backend)
        dl2, ss2, cm = run_chunk(params, dl, ss, ev, valid, cfg)
        outs[backend] = (np.asarray(cm.logits), np.asarray(dl2),
                         np.asarray(ss2.layers.tr))
    for a, b in zip(outs["ref"], outs["pallas-interpret"]):
        np.testing.assert_allclose(b, a, atol=1e-5)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        engine.make_backend(dataclasses.replace(CFG, backend="cuda"))


def test_pallas_backend_accepts_128_aligned_spec():
    """The compiled-kernel backend is granted exactly when every layer's
    N:M blocks tile onto Mosaic (multiples of 128 on both axes)."""
    from repro.core.sparsity import NMSpec

    class Cfg:
        backend = "pallas"
        layer_fanins = (512, 512)

        def __init__(self, block, out_tile):
            self._spec = NMSpec(n=1, m=4, block=block, out_tile=out_tile)

        def spec(self, fan_in):
            return self._spec

    assert engine.make_backend(Cfg(128, 256)) == engine.Backend(
        "pallas", use_kernels=True, interpret=False)
    for block, out_tile in [(1, 1), (128, 64), (64, 128)]:
        with pytest.raises(ValueError, match="multiples of 128"):
            engine.make_backend(Cfg(block, out_tile))
