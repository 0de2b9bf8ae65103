"""Training and serving share ONE timestep engine (core/engine.py).

The load-bearing acceptance property of the engine refactor: `run_chunk`
driven with all-valid, window-aligned chunks from zero deltas must retrace
`run_sample` exactly — logits, traces, adaptive gate thresholds, weight
drift (base updates ≡ accumulated deltas, by linearity of the forward
current), and telemetry — at every depth. One stream vs batch-of-one, so
the training path's batch-shared gate decisions coincide with the serving
path's per-slot decisions. Beyond the uniform default the same trajectory
is pinned where layer fan-ins differ (``n_in=24``: padded layer stack and,
from depth 2, dense ``[S, L, Kmax, N]`` deltas) and on the dense baseline
(``dense=True``).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint
from repro.core import engine
from repro.core.snn import (SNNConfig, init_params, init_state,
                            init_stream_deltas, init_stream_state, run_chunk,
                            run_sample)

N_WINDOWS = 2
CHUNK = 6   # divides t_steps: chunks are window-aligned


GEOMETRIES = {"uniform": {}, "fanin24": dict(n_in=24),
              "dense": dict(dense=True)}


def _cfg(depth, geometry="uniform"):
    return SNNConfig(**{**dict(n_in=32, n_hidden=32, n_layers=depth, n_out=8,
                               t_steps=12, dsst_enabled=False),
                        **GEOMETRIES[geometry]})


@pytest.mark.parametrize("geometry,depth", [
    pytest.param(g, d, id=str(d) if g == "uniform" else f"{g}-{d}")
    for g in GEOMETRIES for d in (1, 2, 3)])
def test_chunk_trajectory_matches_sample(geometry, depth):
    cfg = _cfg(depth, geometry)
    T = cfg.t_steps
    t_wu = int(T * cfg.wu_start_frac)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    ev = (rng.random((N_WINDOWS * T, 1, cfg.n_in)) < 0.3).astype(np.float32)

    # ---- training path: batch of one, learn on, labels off (no SL drift)
    ps, st = params, init_state(cfg, 1)
    tr_logits, tr_sop = [], {"fwd": 0.0, "wu": 0.0, "off": 0.0}
    tr_loss = tr_opens = 0.0
    for w in range(N_WINDOWS):
        ps, st, m = run_sample(ps, st, jnp.asarray(ev[w * T:(w + 1) * T]),
                               None, cfg, learn=True)
        tr_logits.append(np.asarray(m.logits[0]))
        tr_sop["fwd"] += float(m.sop_forward)
        tr_sop["wu"] += float(m.sop_wu)
        tr_sop["off"] += float(m.sop_wu_offered)
        tr_loss += float(m.local_loss) * (T - t_wu)
        tr_opens += float(m.gate_open_frac) * T * cfg.n_layers

    # ---- serving path: one slot, frozen base + delta, window-aligned chunks
    ss, dl = init_stream_state(cfg, 1), init_stream_deltas(cfg, 1)
    sv_logits, sv_sop = [], {"fwd": 0.0, "wu": 0.0, "off": 0.0}
    sv_loss = sv_opens = 0.0
    for c in range(0, N_WINDOWS * T, CHUNK):
        chunk = jnp.asarray(ev[c:c + CHUNK])
        valid = jnp.ones((CHUNK, 1), bool)
        dl, ss, cm = run_chunk(params, dl, ss, chunk, valid, cfg, learn=True)
        for t in np.nonzero(np.asarray(cm.window_end[:, 0]))[0]:
            sv_logits.append(np.asarray(cm.logits[t, 0]))
        sv_sop["fwd"] += float(cm.sop_forward[0])
        sv_sop["wu"] += float(cm.sop_wu[0])
        sv_sop["off"] += float(cm.sop_wu_offered[0])
        sv_loss += float(cm.local_loss[0])
        sv_opens += float(cm.gate_opened[0].sum())

    # window logits (the user-visible predictions)
    assert len(tr_logits) == len(sv_logits) == N_WINDOWS
    for a, b in zip(tr_logits, sv_logits):
        np.testing.assert_allclose(a, b, atol=1e-5)

    # weight drift: in-place base updates == accumulated per-stream delta
    # (uniform fan-ins serve compact [S, L, J, T, bk, bo] deltas; densify
    # over the frozen base's kept-block ids for the dense comparison)
    assert dl.ndim == (6 if engine.geometry(cfg).uniform else 4), dl.shape
    if dl.ndim == 6:
        from repro.core import topology
        idx = topology.stacked_kept_ids(params["hidden"]["mask"], cfg)
        dl = engine.densify_deltas(dl, idx, cfg)
    dl_dense = dl
    drift = np.asarray(ps["hidden"]["w"] - params["hidden"]["w"])
    np.testing.assert_allclose(drift, np.asarray(dl_dense[0]), atol=1e-5)
    # labels never entered: readout identical on both paths
    np.testing.assert_array_equal(np.asarray(ps["readout"]),
                                  np.asarray(params["readout"]))

    # carried state: CC negatives, input trace, window counters, thresholds
    np.testing.assert_allclose(np.asarray(st.layers.tr_cc[:, 0]),
                               np.asarray(ss.layers.tr_cc[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(st.x_tr[0]),
                               np.asarray(ss.x_tr[0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(st.gate.ss_mean),
                               np.asarray(ss.ss_mean[0]), atol=1e-6)
    assert int(st.sample_idx) == int(ss.sample_idx[0]) == N_WINDOWS

    # telemetry: identical energy-model inputs
    for k in ("fwd", "wu", "off"):
        np.testing.assert_allclose(tr_sop[k], sv_sop[k], rtol=1e-6)
    np.testing.assert_allclose(tr_opens, sv_opens, atol=1e-6)
    np.testing.assert_allclose(tr_loss, sv_loss, atol=1e-4)


def test_stacked_params_checkpoint_roundtrip(tmp_path):
    """The stacked layout survives checkpoint save/restore bitwise, and the
    legacy (PR-1 list-of-dicts) layout migrates through stack_params."""
    cfg = _cfg(2)
    params = init_params(jax.random.PRNGKey(1), cfg)
    checkpoint.save(str(tmp_path), 7, params)
    step, back, _ = checkpoint.restore(str(tmp_path), params)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    legacy = engine.unstack_params(params, cfg)
    assert isinstance(legacy["hidden"], list) and len(legacy["hidden"]) == 2
    restacked = engine.stack_params(legacy, cfg)
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(restacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
