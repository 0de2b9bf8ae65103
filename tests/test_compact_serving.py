"""Compact-sparsity-first serving hot path.

The serving layout contract this PR line pins down:

* the per-stream delta tensor is compact ``[S, L, J, T, bk, bo]`` by
  default — only kept N:M blocks are stored;
* the compiled chunk step's jaxpr carries **no dense mask constant and no
  dense delta leaf** (the dense mask exists only on host, at topology
  epoch boundaries);
* storage-level ops — compact<->dense conversion, WU scatter, delta
  projection across topology epochs, lane merge — are **bitwise** exact
  at every kept coordinate;
* whole trajectories agree with the dense baseline to the repo's usual
  1e-5 (compact and dense contractions order float reductions
  differently, so bitwise cross-layout equality is not a real property);
* the compact chunk step is bit-identical between 1 device and an
  8-device slot-sharded mesh (subprocess — device count pins at init).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, strategies as st

from repro.core import engine, topology
from repro.core.dsst import DSSTConfig
from repro.core.snn import (SNNConfig, init_params, init_stream_deltas,
                            init_stream_state, run_chunk, serving_params)
from repro.kernels.nm_spmm import ops as nm_ops, ref as nm_ref
from repro.kernels.wu_outer import ref as wu_ref

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=8,
                dsst_enabled=False)


def _params(seed=0, cfg=CFG):
    return init_params(jax.random.PRNGKey(seed), cfg)


def _events(seed, c, s, cfg=CFG, rate=0.3):
    r = np.random.default_rng(seed)
    return jnp.asarray((r.random((c, s, cfg.n_in)) < rate)
                       .astype(np.float32))


# ------------------------------------------------------------ make_compact

def test_make_compact_traced_mask_needs_n_kept():
    """Regression: under jit a mask is a tracer, and the kept count cannot
    be read off it — the error must say to pass n_kept, not die inside
    a jnp indexing op."""
    spec = CFG.spec(CFG.n_in)
    params = _params()
    w = params["hidden"]["w"][0]
    mask = params["hidden"]["mask"][0]
    bk, bo = spec.block, spec.out_tile

    def f(w, mask):
        return nm_ops.make_compact(w, mask, bk, bo)

    with pytest.raises(ValueError, match="n_kept"):
        jax.jit(f)(w, mask)
    # and with n_kept it traces fine
    t = engine.compact_kept(CFG)
    wc, idx = jax.jit(lambda w, m: nm_ops.make_compact(w, m, bk, bo,
                                                       n_kept=t))(w, mask)
    wc2, idx2 = nm_ops.make_compact(w, mask, bk, bo)
    np.testing.assert_array_equal(np.asarray(wc), np.asarray(wc2))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx2))


# ------------------------------------------------- compact<->dense roundtrip

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2 ** 16), n_slots=st.integers(1, 5))
def test_compact_dense_delta_roundtrip_bitwise(seed, n_slots):
    """densify(compact(x)) == x * dense_mask, bitwise, for any dense delta
    tensor; and compact(densify(c)) == c for any compact one."""
    cfg = CFG
    params = _params(seed % 7, cfg)
    mask = params["hidden"]["mask"]
    idx = topology.stacked_kept_ids(mask, cfg)
    dm = np.asarray(topology.dense_masks(mask, cfg))

    r = np.random.default_rng(seed)
    dense = jnp.asarray(r.standard_normal(
        (n_slots, cfg.n_layers) + dm.shape[1:]).astype(np.float32))
    c = engine.compact_deltas(dense, idx, cfg)
    back = engine.densify_deltas(c, idx, cfg)
    np.testing.assert_array_equal(np.asarray(back),
                                  np.asarray(dense) * dm[None])
    # exact inverse on the kept coordinates
    c2 = engine.compact_deltas(back, idx, cfg)
    np.testing.assert_array_equal(np.asarray(c2), np.asarray(c))


def test_stacked_kept_ids_agree_with_make_compact():
    """topology.stacked_kept_ids and kernels' make_compact must emit the
    same kept-block order — serving gathers with one, checkpoints/epochs
    with the other."""
    cfg = CFG
    params = _params(3, cfg)
    idx = topology.stacked_kept_ids(params["hidden"]["mask"], cfg)
    spec = cfg.spec(cfg.n_in)
    for l in range(cfg.n_layers):
        _, idx_l = nm_ops.make_compact(params["hidden"]["w"][l],
                                       params["hidden"]["mask"][l],
                                       spec.block, spec.out_tile)
        np.testing.assert_array_equal(np.asarray(idx[l]), np.asarray(idx_l))


def test_compact_weights_match_forward():
    """base forward through {"wc", "idx"} == dense masked einsum to 1e-6
    (same math, different reduction order)."""
    cfg = CFG
    params = _params(1, cfg)
    wrep = engine.compact_weights(params["hidden"]["w"],
                                  params["hidden"]["mask"], cfg)
    dm = topology.dense_masks(params["hidden"]["mask"], cfg)
    x = _events(5, 4, 1, cfg)[:, 0, :]           # [4, n_in] spikes
    y_c = nm_ref.nm_spmm(x, wrep["wc"][0], wrep["idx"][0])
    y_d = x @ np.asarray(params["hidden"]["w"][0] * dm[0])
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_d), atol=1e-6)


GEOMETRIES = {"uniform": {}, "fanin24": dict(n_in=24),
              "dense": dict(dense=True)}


@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_forward_current_parity(geometry):
    """engine.fwd_current on every weight rep the geometry allows — dense
    ``{w, mask_f}`` always, compact ``{wc, idx}`` where fan-ins are uniform
    — equals ``pre @ w``, and with per-slot deltas (dense, or compact on
    the compact rep) ``pre[s] @ (w + delta[s])``."""
    cfg = dataclasses.replace(CFG, **GEOMETRIES[geometry])
    geo = engine.geometry(cfg)
    params = _params(0, cfg)
    w, mask = params["hidden"]["w"], params["hidden"]["mask"]
    dm = engine.dense_masks(mask, cfg)
    S = 5
    r = np.random.default_rng(1)
    pre = jnp.asarray(r.standard_normal((S, geo.k_max)).astype(np.float32))
    deltas = jnp.asarray(r.standard_normal(
        (S,) + dm.shape).astype(np.float32)) * dm[None]  # [S, L, Kmax, N]
    reps = {"dense": ({"w": w, "mask_f": dm}, deltas)}
    if geo.uniform:
        idx = topology.stacked_kept_ids(mask, cfg)
        reps["compact"] = (engine.compact_weights(w, mask, cfg),
                           engine.compact_deltas(deltas, idx, cfg))
    assert ("compact" in reps) == (geometry != "fanin24")
    for name, (wrep, dl) in reps.items():
        for l in range(cfg.n_layers):
            w_l = jax.tree_util.tree_map(lambda a: a[l], wrep)
            np.testing.assert_allclose(
                np.asarray(engine.fwd_current(pre, w_l, None)),
                np.asarray(pre @ w[l]), atol=1e-5, err_msg=name)
            want = jnp.einsum("sk,skn->sn", pre, w[l][None] + deltas[:, l])
            np.testing.assert_allclose(
                np.asarray(engine.fwd_current(pre, w_l, dl[:, l])),
                np.asarray(want), atol=1e-5, err_msg=name)


# ----------------------------------------------------- projection bitwise

@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2 ** 16))
def test_project_deltas_compact_matches_dense_bitwise(seed):
    """Across a topology swap: projecting in the compact layout == project
    dense then re-compact, bitwise. Surviving blocks keep their exact
    bits; recycled coordinates restart at zero."""
    cfg = dataclasses.replace(CFG, dsst=DSSTConfig(period=4, prune_frac=0.5),
                              dsst_enabled=True)
    old = _params(seed % 11, cfg)["hidden"]["mask"]
    new = _params((seed % 11) + 1, cfg)["hidden"]["mask"]
    old_ids = topology.stacked_kept_ids(old, cfg)
    new_ids = topology.stacked_kept_ids(new, cfg)

    r = np.random.default_rng(seed)
    dm_old = np.asarray(topology.dense_masks(old, cfg))
    dense = jnp.asarray((r.standard_normal(
        (3, cfg.n_layers) + dm_old.shape[1:]) * dm_old[None])
        .astype(np.float32))
    compact = engine.compact_deltas(dense, old_ids, cfg)

    proj_dense = topology.project_deltas(dense, old, new, cfg)
    proj_compact = topology.project_deltas(compact, old, new, cfg)
    # the dispatcher and the explicit-id entry point agree exactly
    np.testing.assert_array_equal(
        np.asarray(topology.project_deltas_compact(compact, old_ids,
                                                   new_ids)),
        np.asarray(proj_compact))
    np.testing.assert_array_equal(
        np.asarray(engine.densify_deltas(proj_compact, new_ids, cfg)),
        np.asarray(proj_dense))
    # survivors bit-preserved
    np.testing.assert_array_equal(
        np.asarray(engine.compact_deltas(proj_dense, new_ids, cfg)),
        np.asarray(proj_compact))


# ------------------------------------------------------- mask-free jaxpr

def test_serving_jaxpr_has_no_dense_mask_or_dense_deltas():
    """THE tentpole assert: with mask-free exec params and compact deltas
    the chunk jaxpr contains no f32 leaf shaped like the dense mask
    [L, Kmax, N] or the dense delta tensor [S, L, Kmax, N] — neither as a
    constant nor as an intermediate. Since the static-analysis PR the
    hand-rolled jaxpr walk lives in repro.analysis (mask_free /
    no_dense_deltas check avals recursively AND cross-check the printed
    jaxpr — belt and braces for consts a traversal might miss); this test
    pins those contracts to the real chunk entrypoint."""
    from repro import analysis

    cfg = CFG
    S, C = 4, 6
    params = _params(0, cfg)
    sp_exec = serving_params(params, cfg)
    dc = init_stream_deltas(cfg, S)
    st0 = init_stream_state(cfg, S)
    ev = _events(0, C, S, cfg)
    valid = jnp.ones((C, S), bool)

    report = analysis.check(
        lambda p, d, s: run_chunk(p, d, s, ev, valid, cfg),
        (sp_exec, dc, st0),
        [analysis.mask_free(cfg), analysis.no_dense_deltas(cfg, S)])
    report.raise_if_violations()
    assert report.ok and set(report.contracts) == {"mask_free",
                                                  "no_dense_deltas"}


@pytest.mark.parametrize("dense", [False, True])
def test_dense_baseline_still_runs_and_matches(dense):
    """The dense path stays selectable (compact=False) and the two layouts
    track each other at the repo's trajectory tolerance — on the N:M net
    and on the dense baseline net (``dense=True``)."""
    cfg = dataclasses.replace(CFG, dense=dense)
    S, C = 4, 8
    params = _params(0, cfg)
    ev = _events(1, C, S, cfg)
    valid = jnp.asarray(np.random.default_rng(2).random((C, S)) < 0.85)
    st0 = init_stream_state(cfg, S)

    dc, _, mc = run_chunk(serving_params(params, cfg),
                          init_stream_deltas(cfg, S), st0, ev, valid, cfg)
    dd, _, md = run_chunk(params, init_stream_deltas(cfg, S, compact=False),
                          st0, ev, valid, cfg)
    idx = topology.stacked_kept_ids(params["hidden"]["mask"], cfg)
    np.testing.assert_allclose(
        np.asarray(engine.densify_deltas(dc, idx, cfg)), np.asarray(dd),
        atol=1e-5)
    np.testing.assert_allclose(np.asarray(mc.logits), np.asarray(md.logits),
                               atol=1e-5)


# ------------------------------------------------------------- WU bitwise

@pytest.mark.parametrize("n_in,n_hidden", [(32, 32), (32, 64), (64, 32)])
def test_wu_outer_slots_bitwise_vs_dense_at_kept_coords(n_in, n_hidden):
    """One WU step: the compact scatter == the dense masked outer product,
    bitwise, because the multiply association is mirrored — also where
    the layer is not square."""
    cfg = dataclasses.replace(CFG, n_in=n_in, n_hidden=n_hidden, n_layers=1)
    spec = cfg.spec(cfg.n_in)
    params = _params(4, cfg)
    idx = topology.stacked_kept_ids(params["hidden"]["mask"], cfg)[0]
    dm = np.asarray(topology.dense_masks(params["hidden"]["mask"], cfg)[0])

    S = 5
    r = np.random.default_rng(7)
    pre = jnp.asarray(r.standard_normal((S, cfg.n_in)).astype(np.float32))
    mod = jnp.asarray(r.standard_normal((S, cfg.n_hidden)).astype(np.float32))
    scale = jnp.asarray(r.uniform(0, 0.1, S).astype(np.float32))

    dwc = wu_ref.wu_outer_slots(pre, mod, idx, scale, spec.block,
                                spec.out_tile)
    dense = (scale[:, None] * pre)[:, :, None] * mod[:, None, :] * dm[None]
    got = engine.densify_deltas(dwc[:, None], idx[None], cfg)[:, 0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(dense))


# ------------------------------------------------------------- merge fold

def test_merge_lane_into_base_fold_exact_compact():
    """Folding a compact lane into the base then serving with a zero lane
    == serving with the lane, to fp tolerance; and the merge itself is a
    bitwise densify-add (base is exactly zero off-mask)."""
    from repro.serving.adapt import merge_lane_into_base

    cfg = CFG
    S, C = 2, 8
    params = _params(0, cfg)
    ev = _events(3, C, S, cfg)
    valid = jnp.ones((C, S), bool)
    st0 = init_stream_state(cfg, S)
    dl, _, _ = run_chunk(serving_params(params, cfg),
                         init_stream_deltas(cfg, S), st0, ev, valid, cfg)

    merged = merge_lane_into_base(params, dl, 0, cfg, weight=1.0)
    idx = topology.stacked_kept_ids(params["hidden"]["mask"], cfg)
    lane_dense = engine.densify_deltas(dl[:1], idx, cfg)[0]
    np.testing.assert_array_equal(
        np.asarray(merged["hidden"]["w"]),
        np.asarray(params["hidden"]["w"] + lane_dense))
    # base stays exactly zero off the mask — the invariant that makes the
    # mask-free merge exact
    dm = np.asarray(topology.dense_masks(params["hidden"]["mask"], cfg))
    assert np.all(np.asarray(merged["hidden"]["w"])[dm == 0] == 0)

    # fold-exactness: folded base + zero lane == old base + lane, to fp
    ev2 = _events(4, C, S, cfg)
    _, _, m_lane = run_chunk(serving_params(params, cfg), dl, st0, ev2,
                             valid, cfg, learn=False)
    zero0 = dl.at[0].set(0.0)
    _, _, m_fold = run_chunk(serving_params(merged, cfg), zero0, st0, ev2,
                             valid, cfg, learn=False)
    np.testing.assert_allclose(np.asarray(m_fold.logits[:, 0]),
                               np.asarray(m_lane.logits[:, 0]), atol=1e-5)


# ------------------------------------------------------- checkpoint shim

def test_fleet_checkpoint_roundtrip_and_migration(tmp_path):
    from repro.serving import restore_fleet, save_fleet

    cfg = CFG
    S, C = 3, 8
    params = _params(0, cfg)
    ev = _events(6, C, S, cfg)
    valid = jnp.ones((C, S), bool)
    st0 = init_stream_state(cfg, S)
    dc, stc, _ = run_chunk(serving_params(params, cfg),
                           init_stream_deltas(cfg, S), st0, ev, valid, cfg)

    # compact-stored -> compact fleet: bitwise roundtrip
    save_fleet(str(tmp_path / "c"), 5, params, dc, stc)
    step, p2, d2, s2, extra = restore_fleet(str(tmp_path / "c"), cfg)
    assert step == 5 and extra["delta_layout"] == "compact"
    assert extra["n_slots"] == S
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(dc))
    for a, b in zip(jax.tree_util.tree_leaves((params, stc)),
                    jax.tree_util.tree_leaves((p2, s2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # dense-stored (pre-compact checkpoint) -> compact fleet: migrated
    # bit-exactly at every kept coordinate
    idx = topology.stacked_kept_ids(params["hidden"]["mask"], cfg)
    dd = engine.densify_deltas(dc, idx, cfg)
    save_fleet(str(tmp_path / "d"), 9, params, dd, stc)
    step, _, d3, _, extra = restore_fleet(str(tmp_path / "d"), cfg,
                                          compact=True)
    assert step == 9 and extra["delta_layout"] == "dense"
    np.testing.assert_array_equal(np.asarray(d3), np.asarray(dc))

    # compact-stored -> dense fleet densifies
    _, _, d4, _, _ = restore_fleet(str(tmp_path / "c"), cfg, compact=False)
    np.testing.assert_array_equal(np.asarray(d4), np.asarray(dd))


# ------------------------------------------------- scheduler + telemetry

def test_scheduler_compact_by_default_reports_bytes_held():
    from repro.serving import ReplaySource, StreamScheduler, StreamSession

    cfg = CFG
    params = _params(0, cfg)
    sched = StreamScheduler(params, cfg, n_slots=2, chunk_len=4)
    assert sched.compact and sched.deltas.ndim == 6
    ev = (np.random.default_rng(0).random((2 * cfg.t_steps, cfg.n_in))
          < 0.3).astype(np.float32)
    sched.submit(StreamSession(sid=0, source=ReplaySource(ev)))
    sched.run_until_drained()
    bh = sched.telemetry.bytes_held()
    assert bh["total"] == bh["params"] + bh["deltas"] > 0
    assert bh["deltas"] == sched.deltas.nbytes
    # compact holds strictly less than the dense baseline would
    dense = init_stream_deltas(cfg, 2, compact=False)
    assert bh["deltas"] < dense.nbytes
    assert sched.telemetry.rollup()["bytes_held"]["total"] == bh["total"]
    # the gauge is in the obs registry for scraping
    fam = sched.telemetry.registry.get("serving_bytes_held")
    assert fam is not None


def test_scheduler_dense_vs_compact_trajectory_parity_evolving():
    """Full fleet with live DSST epochs: compact and dense layouts make the
    same epoch decisions and agree on every prediction to 1e-5."""
    from repro.serving import (ReplaySource, StreamScheduler, StreamSession,
                               TopologyService, TopologyServiceConfig)

    cfg = dataclasses.replace(
        CFG, t_steps=12, dsst=DSSTConfig(period=4, prune_frac=0.5),
        dsst_enabled=True)
    params = _params(0, cfg)

    def drive(compact):
        svc = TopologyService(cfg, TopologyServiceConfig(epoch_every=3,
                                                         merge_top=1))
        sched = StreamScheduler(params, cfg, n_slots=4, chunk_len=6,
                                topology=svc, compact=compact)
        for sid in range(4):
            ev = (np.random.default_rng(sid).random((36, cfg.n_in))
                  < 0.35).astype(np.float32)
            sched.submit(StreamSession(sid=sid, source=ReplaySource(
                ev, chunk_len=6), adapt=(sid % 2 == 0)))
        done = {s.sid: s for s in sched.run_until_drained()}
        return sched, svc, done

    sc, vc, dc = drive(True)
    sd, vd, dd = drive(False)
    assert sc.compact and not sd.compact
    assert vc.epoch_idx == vd.epoch_idx >= 1
    assert [(e.pruned, e.regrown) for e in vc.events] \
        == [(e.pruned, e.regrown) for e in vd.events]
    assert sc.n_compiles == 1 and sd.n_compiles == 1
    for sid in dc:
        assert len(dc[sid].predictions) == len(dd[sid].predictions) > 0
        for a, b in zip(dc[sid].predictions, dd[sid].predictions):
            np.testing.assert_allclose(a.logits, b.logits, atol=1e-5)
    # compact fleet held strictly less weight-state than the dense one
    assert sc.telemetry.bytes_held()["total"] \
        < sd.telemetry.bytes_held()["total"]


# ------------------------------------------------------------- 8 devices

def test_compact_chunk_step_8device_bit_identical():
    """The compact chunk step under 8-device slot-axis shard_map is
    bit-identical to 1 device (subprocess: device count pins at init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.snn import (SNNConfig, init_params, init_stream_state,
                                    init_stream_deltas, serving_params)
        from repro.launch import sharding as SH
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.adapt import AdaptConfig, make_chunk_fn

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8,
                        t_steps=16)
        params = init_params(jax.random.PRNGKey(0), cfg)
        sp = serving_params(params, cfg)
        mesh = make_serving_mesh()
        assert SH.slot_devices(mesh) == 8
        S, C = 16, 6
        rng = np.random.default_rng(0)
        adapt = AdaptConfig(delta_decay=0.95, delta_clip=0.3)
        fn1 = make_chunk_fn(cfg, adapt)
        fn8 = make_chunk_fn(cfg, adapt, mesh=mesh)
        st1 = init_stream_state(cfg, S)
        dl1 = init_stream_deltas(cfg, S)
        assert dl1.ndim == 6, dl1.shape            # compact by default
        st8 = jax.device_put(st1, SH.stream_shardings(st1, mesh))
        dl8 = jax.device_put(dl1, SH.slot_sharding(mesh))
        for i in range(3):
            events = (rng.random((C, S, cfg.n_in)) < 0.3).astype(np.float32)
            valid = rng.random((C, S)) < 0.8
            amask = rng.random(S) < 0.7
            dl1, st1, m1 = fn1(sp, dl1, st1, events, valid, amask)
            dl8, st8, m8 = fn8(sp, dl8, st8, events, valid, amask)
        assert dl8.sharding.spec == SH.slot_spec(0), dl8.sharding
        np.testing.assert_array_equal(np.asarray(dl1), np.asarray(dl8))
        for a, b in zip(jax.tree_util.tree_leaves(st1),
                        jax.tree_util.tree_leaves(st8)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for name, a, b in zip(m1._fields, m1, m8):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        assert fn1.n_traces() == 1 and fn8.n_traces() == 1
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout
