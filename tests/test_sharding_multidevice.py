"""Sharding rules + distributed semantics on an 8-device host mesh.

Device count must be pinned before jax initializes, so these run in a
subprocess with XLA_FLAGS set (conftest keeps the main process at 1 device).
"""
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_rules_produce_valid_shardings_and_train_step_runs():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        import repro.configs as C
        from repro.launch import sharding as SH
        from repro.launch.train import TrainHParams, make_train_step, init_train_state
        from repro.optim import adamw_init

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
        cfg = C.get_reduced("phi3_medium_14b")
        hp = TrainHParams()
        params, opt, ss = init_train_state(jax.random.PRNGKey(0), cfg, hp)
        p_sh = SH.tree_shardings(params, cfg, mesh)
        o_sh = SH.tree_shardings(opt, cfg, mesh)
        ss_sh = jax.tree.map(lambda _: SH.replicated(mesh), ss)
        batch = {"tokens": jnp.zeros((8, 16), jnp.int32),
                 "labels": jnp.zeros((8, 16), jnp.int32)}
        b_sh = SH.batch_shardings(batch, mesh)
        with mesh:
            fn = jax.jit(make_train_step(cfg, hp),
                         in_shardings=(p_sh, o_sh, ss_sh, b_sh),
                         out_shardings=(p_sh, o_sh, ss_sh, None))
            params = jax.device_put(params, p_sh)
            opt = jax.device_put(opt, o_sh)
            batch = jax.device_put(batch, b_sh)
            p2, o2, s2, m = fn(params, opt, ss, batch)
            assert not bool(jnp.isnan(m["loss"])), m
            # attention projection really is sharded over model axis
            wq = p2["layers"]["attn"]["wq"]["w"]
            assert "model" in wq.sharding.spec, wq.sharding
        print("OK loss", float(m["loss"]))
    """))


def test_sharded_equals_single_device():
    """The same step on a (2,4) mesh and on 1 device gives the same loss."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        import repro.configs as C
        from repro.launch import sharding as SH
        from repro.launch.train import TrainHParams, make_train_step, init_train_state

        cfg = C.get_reduced("stablelm_12b")
        hp = TrainHParams()
        params, opt, ss = init_train_state(jax.random.PRNGKey(0), cfg, hp)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab),
                 "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, cfg.vocab)}
        step = make_train_step(cfg, hp)
        _,_,_, m1 = jax.jit(step)(params, opt, ss, batch)

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
        p_sh = SH.tree_shardings(params, cfg, mesh)
        b_sh = SH.batch_shardings(batch, mesh)
        with mesh:
            fn = jax.jit(step, in_shardings=(p_sh, None, None, b_sh))
            _,_,_, m2 = fn(jax.device_put(params, p_sh), opt, ss,
                           jax.device_put(batch, b_sh))
        d = abs(float(m1["loss"]) - float(m2["loss"]))
        assert d < 1e-3, (float(m1["loss"]), float(m2["loss"]))
        print("OK", float(m1["loss"]), float(m2["loss"]))
    """))


def test_compressed_dp_allreduce_shardmap():
    """int8+EF gradient compression under shard_map psum: mean of
    decompressed per-replica grads ~= uncompressed mean."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.runtime.compression import CompressionConfig, compress, decompress

        mesh = Mesh(np.asarray(jax.devices()).reshape(8), ("data",))
        cfg = CompressionConfig(kind="int8")
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        @functools.partial(shard_map, mesh=mesh, in_specs=P("data", None),
                           out_specs=P(None))
        def mean_compressed(gl):
            rec = decompress(compress(gl[0], cfg), cfg)
            return jax.lax.pmean(rec, "data")[None]

        got = mean_compressed(g)[0]
        want = g.mean(0)
        assert float(jnp.abs(got - want).max()) < 0.02, float(jnp.abs(got-want).max())
        print("OK")
    """))


def test_dryrun_entry_on_8_devices():
    """The dry-run machinery end-to-end on a small mesh + reduced config."""
    print(_run("""
        import jax, numpy as np
        from jax.sharding import Mesh
        import repro.configs as C
        from repro.configs.base import ShapeConfig
        # note: importing dryrun pins 512 host devices (its first lines);
        # the test mesh just uses the first 8.
        from repro.launch.dryrun import lower_cell, input_specs
        from repro.launch.train import TrainHParams

        cfg = C.get_reduced("mixtral_8x7b")
        shape = ShapeConfig("smoke", 32, 8, "train")
        mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("data", "model"))
        rec = lower_cell(cfg, shape, mesh, hp=TrainHParams(), cost_probes=True)
        assert rec["flops_per_device"] > 0
        assert rec["memory"]["peak_estimate_bytes"] > 0
        shape_d = ShapeConfig("smoke_d", 64, 8, "decode")
        rec2 = lower_cell(cfg, shape_d, mesh, cost_probes=False)
        assert rec2["compile_s"] > 0
        print("OK", rec["flops_per_device"], rec2["raw"]["flops_per_device"])
    """))
