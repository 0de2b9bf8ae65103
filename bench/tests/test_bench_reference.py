"""The plain reference against the program, on the CPU at a small size.

A sound program agrees with the reference (``test_bench_faults`` runs the
sound cells, and weights held in bfloat16); here an error planted in one
layer of the program at a time must make ``correct`` false.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import snn as ref
from bench.tests.small import SMALL_MODEL, run_small, small_cell


def _plant(monkeypatch, layer):
    """Break one layer of the program (only inside this test)."""
    from repro.core import engine, gating, snn, topology
    from repro.kernels.nm_spmm import ref as nm_ref
    from repro.kernels.wu_outer import ref as wu_ref

    def scale(mod, name, k):
        orig = getattr(mod, name)
        monkeypatch.setattr(mod, name, lambda *a, **kw: orig(*a, **kw) * k)

    if layer == "base_forward":         # serving's compact forward
        scale(nm_ref, "nm_spmm", 1.05)
    elif layer == "dense_forward":      # training's dense forward
        scale(engine, "fwd_current", 1.05)
    elif layer == "delta_current":
        scale(nm_ref, "nm_spmm_deltas", 0.0)
    elif layer == "lif":
        orig = engine.lif_step
        monkeypatch.setattr(engine, "lif_step",
                            lambda v, tr, cur, *, alpha, beta, theta: orig(
                                v, tr, cur, alpha=alpha * 0.97, beta=beta,
                                theta=theta))
    elif layer == "gate":
        orig = gating.gate_decide

        def always_open(ss_mean, ia, ss, cfg):
            op, mean = orig(ss_mean, ia, ss, cfg)
            return jnp.ones_like(op), mean
        monkeypatch.setattr(gating, "gate_decide", always_open)
    elif layer == "serve_wu":
        scale(wu_ref, "wu_outer_slots", 2.0)
    elif layer == "modulator":
        orig = engine.ossl_modulator
        mod = lambda *a: orig(*a) * 1.5  # noqa: E731
        monkeypatch.setattr(engine, "ossl_modulator", mod)
        monkeypatch.setattr(snn, "ossl_modulator", mod)
    elif layer == "readout_rule":
        from bench.modes import train
        orig = train._snn_config
        monkeypatch.setattr(
            train, "_snn_config",
            lambda cfg: orig({**cfg, "lr_out": 2 * cfg["lr_out"]}))
    elif layer == "dsst":
        orig = topology.prune_regrow_factored_stacked
        monkeypatch.setattr(
            topology, "prune_regrow_factored_stacked",
            lambda mask, ws, pre, post, spec, k: orig(mask, ws, -pre, post,
                                                      spec, k))
    else:
        raise ValueError(layer)


@pytest.mark.parametrize("layer", ["base_forward", "delta_current", "lif",
                                   "gate", "serve_wu", "modulator"])
def test_serving_layer_error_is_caught(monkeypatch, layer):
    _plant(monkeypatch, layer)
    out = run_small("serve_steady")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("layer", ["dense_forward", "lif", "gate",
                                   "modulator", "readout_rule", "dsst"])
def test_training_layer_error_is_caught(monkeypatch, layer):
    _plant(monkeypatch, layer)
    out = run_small("train_dsst")
    assert not out["correct"], out["checks"]


def test_nm_counts_follow_the_paper():
    m, n = ref.nm_counts({"n_in": 512, "sparsity": 0.8})
    assert (m, n) == (128, 26)     # 4 groups, 20 % kept (rounded)
    assert ref.dsst_k({"n_in": 512, "sparsity": 0.8,
                       "dsst": {"prune_frac": 0.25}}) == 6


def test_prune_regrow_keeps_n_per_group():
    cfg = {**small_cell("train_dsst").config, **SMALL_MODEL}
    m, n = ref.nm_counts(cfg)
    rng = np.random.default_rng(0)
    K, N = cfg["n_in"], cfg["n_hidden"]
    scores = rng.random((K // m, m, N))
    mask = (np.argsort(np.argsort(scores, 1), 1) < n).reshape(K, N)
    w = rng.normal(size=(K, N)) * mask
    w2, mask2 = ref.prune_regrow(cfg, jnp.asarray(w, jnp.float32),
                                 jnp.asarray(mask),
                                 jnp.asarray(rng.random(K)))
    mask2 = np.asarray(mask2)
    assert (mask2.reshape(K // m, m, N).sum(1) == n).all()
    assert np.all(np.asarray(w2)[~mask2] == 0)
    assert (mask2 != mask).any()
