"""Sharded slot grid: multi-device parity for the serving chunk step.

The slot axis is the shardable axis by construction (every per-stream
quantity is slot-leading; the chunk step never reduces over slots —
asserted in core/engine.scan_chunk). These tests pin the consequence: the
same chunk step on a 1-device grid and under slot-axis ``shard_map`` on an
8-device host mesh is **bit-identical** — deltas, every StreamState leaf,
and all metrics — and the scheduler still compiles exactly once.

Device count must be pinned before jax initializes, so the 8-device cases
run in a subprocess with XLA_FLAGS set (conftest keeps the main process at
1 device); helper-level rules are tested in-process on a 1-device mesh.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


# ------------------------------------------------------------ in-process

def test_slot_axis_rules_single_device_mesh():
    from repro.core.snn import SNNConfig, init_stream_state
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh(1)
    assert SH.slot_devices(mesh) == 1
    assert SH.round_up_slots(5, mesh) == 5
    st = init_stream_state(SNNConfig(n_in=8, n_hidden=8, n_out=4), 4)
    shs = SH.stream_shardings(st, mesh)
    for sh in jax.tree_util.tree_leaves(shs):
        assert sh.spec == SH.slot_spec(0), sh.spec
    in_specs, out_specs = SH.chunk_step_specs()
    assert in_specs[0] == jax.sharding.PartitionSpec()      # params replicate
    assert out_specs[2].logits == SH.slot_spec(1)           # [C, S, n_out]


def test_round_up_and_divisibility():
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_serving_mesh

    mesh = make_serving_mesh(1)
    assert SH.round_up_slots(1, mesh) == 1
    SH.check_slot_divisible(3, mesh)    # 1 device divides anything
    with pytest.raises(RuntimeError, match="xla_force_host_platform"):
        make_serving_mesh(4096)


def test_mesh_scheduler_pads_slot_grid_single_device():
    """Device-count-aware allocation: on a 1-device mesh the grid is only
    padded up to the 2-slots-per-device bit-identity floor."""
    from repro.core.snn import SNNConfig, init_params
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import StreamScheduler

    cfg = SNNConfig(n_in=8, n_hidden=8, n_layers=1, n_out=4, t_steps=4)
    params = init_params(jax.random.PRNGKey(0), cfg)
    sched = StreamScheduler(params, cfg, n_slots=1, mesh=make_serving_mesh(1))
    assert sched.n_slots == 2
    sched = StreamScheduler(params, cfg, n_slots=3, mesh=make_serving_mesh(1))
    assert sched.n_slots == 3


# ------------------------------------------------------------ 8 devices

def test_sharded_chunk_step_bit_identical_and_compiles_once():
    """3 carried chunk steps, ragged valid, mixed adapt mask, decay+clip on:
    1-device vs 8-device shard_map paths agree bit-for-bit everywhere."""
    print(_run("""
        import numpy as np, jax
        from repro.core.snn import (SNNConfig, init_params, init_stream_state,
                                    init_stream_deltas)
        from repro.launch import sharding as SH
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.adapt import AdaptConfig, make_chunk_fn

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
        params = init_params(jax.random.PRNGKey(0), cfg)
        mesh = make_serving_mesh()
        assert SH.slot_devices(mesh) == 8
        S, C = 16, 6
        rng = np.random.default_rng(0)
        adapt = AdaptConfig(delta_decay=0.95, delta_clip=0.3)
        fn1 = make_chunk_fn(cfg, adapt)
        fn8 = make_chunk_fn(cfg, adapt, mesh=mesh)
        st1, dl1 = init_stream_state(cfg, S), init_stream_deltas(cfg, S)
        st8 = jax.device_put(st1, SH.stream_shardings(st1, mesh))
        dl8 = jax.device_put(dl1, SH.slot_sharding(mesh))
        for i in range(3):
            events = (rng.random((C, S, cfg.n_in)) < 0.3).astype(np.float32)
            valid = rng.random((C, S)) < 0.8
            amask = rng.random(S) < 0.7
            dl1, st1, m1 = fn1(params, dl1, st1, events, valid, amask)
            dl8, st8, m8 = fn8(params, dl8, st8, events, valid, amask)
        assert dl8.sharding.spec == SH.slot_spec(0), dl8.sharding
        np.testing.assert_array_equal(np.asarray(dl1), np.asarray(dl8))
        for a, b in zip(jax.tree_util.tree_leaves(st1),
                        jax.tree_util.tree_leaves(st8)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for name, a, b in zip(m1._fields, m1, m8):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
        assert fn1.n_traces() == 1 and fn8.n_traces() == 1, \\
            (fn1.n_traces(), fn8.n_traces())
        print("OK")
    """))


def test_sharded_scheduler_end_to_end_parity():
    """Full lifecycle on the mesh — admits, lane surgery on sharded arrays,
    retires — produces the same predictions/deltas as the 1-device grid,
    pads n_slots to the device count, and compiles exactly once."""
    print(_run("""
        import numpy as np, jax
        from repro.core.snn import SNNConfig, init_params
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import ReplaySource, StreamScheduler, StreamSession

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
        params = init_params(jax.random.PRNGKey(0), cfg)

        def events(seed, t, rate=0.3):
            r = np.random.default_rng(seed)
            return (r.random((t, cfg.n_in)) < rate).astype(np.float32)

        def drive(mesh, n_slots):
            sched = StreamScheduler(params, cfg, n_slots=n_slots,
                                    chunk_len=5, mesh=mesh)
            for sid in range(6):
                sched.submit(StreamSession(
                    sid=sid, source=ReplaySource(events(sid, 2 * cfg.t_steps)),
                    adapt=(sid % 2 == 0)))
            done = {s.sid: s for s in sched.run_until_drained()}
            return sched, done

        s1, d1 = drive(None, 16)
        s8, d8 = drive(make_serving_mesh(), 6)   # pads to 16 (2 per device)
        assert s8.n_slots == 16, s8.n_slots
        assert s1.n_compiles == 1 and s8.n_compiles == 1, \\
            (s1.n_compiles, s8.n_compiles)
        for sid in d1:
            assert len(d1[sid].predictions) == len(d8[sid].predictions) == 2
            for a, b in zip(d1[sid].predictions, d8[sid].predictions):
                np.testing.assert_array_equal(a.logits, b.logits)
            np.testing.assert_array_equal(d1[sid].final_deltas,
                                          d8[sid].final_deltas)
        print("OK")
    """))


def test_sharded_topology_evolution_parity():
    """Live DSST epochs on the 8-device slot-sharded grid: bit-identical to
    the 1-device fleet (evolved base, deltas, predictions, epoch history),
    the swap preserves the slot sharding, and the chunk step compiles
    exactly once on both paths — the zero-recompile topology-swap
    guarantee under shard_map."""
    print(_run("""
        import numpy as np, jax
        from repro.core.dsst import DSSTConfig
        from repro.core.snn import SNNConfig, init_params
        from repro.core import topology
        from repro.launch import sharding as SH
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import (ReplaySource, StreamScheduler,
                                   StreamSession, TopologyService,
                                   TopologyServiceConfig)

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8,
                        t_steps=12, dsst=DSSTConfig(period=4, prune_frac=0.5))
        params = init_params(jax.random.PRNGKey(0), cfg)

        def events(seed, t, rate=0.3):
            r = np.random.default_rng(seed)
            return (r.random((t, cfg.n_in)) < rate).astype(np.float32)

        def drive(mesh):
            svc = TopologyService(cfg, TopologyServiceConfig(
                epoch_every=3, merge_top=1))
            sched = StreamScheduler(params, cfg, n_slots=16, chunk_len=6,
                                    mesh=mesh, topology=svc)
            for sid in range(6):
                sched.submit(StreamSession(
                    sid=sid, source=ReplaySource(events(sid, 54),
                                                 chunk_len=6),
                    adapt=(sid % 2 == 0)))
            done = {s.sid: s for s in sched.run_until_drained()}
            return sched, svc, done

        s1, v1, d1 = drive(None)
        s8, v8, d8 = drive(make_serving_mesh())
        assert v1.epoch_idx >= 2 and v8.epoch_idx == v1.epoch_idx, \\
            (v1.epoch_idx, v8.epoch_idx)
        assert sum(e.pruned for e in v1.events) > 0
        assert s1.n_compiles == 1 and s8.n_compiles == 1, \\
            (s1.n_compiles, s8.n_compiles)
        # epoch-for-epoch identical evolution
        assert [(e.pruned, e.regrown, e.merged_slots) for e in v1.events] \\
            == [(e.pruned, e.regrown, e.merged_slots) for e in v8.events]
        # the swap preserved the slot sharding of the delta grid
        assert s8.deltas.sharding.spec == SH.slot_spec(0), s8.deltas.sharding
        # evolved base + deltas bit-identical across paths, invariant holds
        assert topology.check(s8.params["hidden"]["mask"], cfg)
        for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                        jax.tree_util.tree_leaves(s8.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(s1.deltas),
                                      np.asarray(s8.deltas))
        for sid in d1:
            assert len(d1[sid].predictions) == len(d8[sid].predictions) > 0
            for a, b in zip(d1[sid].predictions, d8[sid].predictions):
                np.testing.assert_array_equal(a.logits, b.logits)
        print("OK")
    """))


def test_live_fleet_4device_bit_identical_factors_and_epochs():
    """Live DSST on a 4-device slot mesh against one device: every grid
    step's cross-device factor sums (each device's shard reduced in the
    step, the four partials combined by the adjacent-pair tree), every
    jitted epoch's delta norms and hot lanes, and the evolved base, delta
    grid and window logits are the same bits; the chunk step and the
    epoch program each compile once."""
    print(_run("""
        import numpy as np, jax
        from repro.core.dsst import DSSTConfig
        from repro.core.snn import SNNConfig, init_params
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import (ReplaySource, StreamScheduler,
                                   StreamSession, TopologyService,
                                   TopologyServiceConfig)

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8,
                        t_steps=12, dsst=DSSTConfig(period=4, prune_frac=0.5))
        params = init_params(jax.random.PRNGKey(3), cfg)

        class Recording(TopologyService):
            def __init__(self, *a):
                super().__init__(*a)
                self.factors, self.records = [], []
            def observe(self, m):
                self.factors.append((np.asarray(m.pre_mag),
                                     np.asarray(m.post_mag)))
                super().observe(m)
            def enqueue(self, *a, **k):
                run = super().enqueue(*a, **k)
                self.records.append(run.record)
                return run

        def drive(mesh):
            svc = Recording(cfg, TopologyServiceConfig(
                epoch_every=2, merge_top=2))
            sched = StreamScheduler(params, cfg, n_slots=8, chunk_len=6,
                                    mesh=mesh, topology=svc,
                                    pipeline_depth=1)
            for sid in range(8):
                r = np.random.default_rng(sid)
                ev = (r.random((48, cfg.n_in)) < 0.3).astype(np.float32)
                sched.submit(StreamSession(
                    sid=sid, source=ReplaySource(ev, chunk_len=6)))
            done = {s.sid: s for s in sched.run_until_drained()}
            return sched, svc, done

        s1, v1, d1 = drive(None)
        s4, v4, d4 = drive(make_serving_mesh(4))
        assert s4.n_slots == 8 and v1.epoch_idx >= 2
        # 3 slots a device round up to 4: a shard is an exact subtree
        assert StreamScheduler(params, cfg, n_slots=12,
                               mesh=make_serving_mesh(4),
                               topology=Recording(cfg)).n_slots == 16
        assert v4.epoch_idx == v1.epoch_idx
        assert s1.n_compiles == 1 and s4.n_compiles == 1, \\
            (s1.n_compiles, s4.n_compiles)
        assert v1.n_program_traces == 1 and v4.n_program_traces == 1, \\
            (v1.n_program_traces, v4.n_program_traces)
        assert len(v1.factors) == len(v4.factors) > 0
        for (a, b), (c, d) in zip(v1.factors, v4.factors):
            np.testing.assert_array_equal(a, c)
            np.testing.assert_array_equal(b, d)
        for r1, r4 in zip(v1.records, v4.records):
            np.testing.assert_array_equal(np.asarray(r1.norms),
                                          np.asarray(r4.norms))
            np.testing.assert_array_equal(np.asarray(r1.hot),
                                          np.asarray(r4.hot))
            np.testing.assert_array_equal(np.asarray(r1.hot_deltas),
                                          np.asarray(r4.hot_deltas))
        assert [e.merged_slots for e in v1.events] == \
            [e.merged_slots for e in v4.events]
        assert any(e.merged_slots for e in v1.events)
        for a, b in zip(jax.tree_util.tree_leaves(s1.params),
                        jax.tree_util.tree_leaves(s4.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(s1.deltas),
                                      np.asarray(s4.deltas))
        for sid in d1:
            assert len(d1[sid].predictions) == len(d4[sid].predictions) > 0
            for a, b in zip(d1[sid].predictions, d4[sid].predictions):
                np.testing.assert_array_equal(a.logits, b.logits)
        print("OK")
    """, devices=4))
