"""Compiles of the SNN main path for a described TPU v5e (no chip needed).

The TPU compiler ships with libtpu and compiles for a chip that is
described rather than attached, so these tests refuse here what the chip's
compiler would refuse — a block shape Mosaic cannot tile, a program that
does not fit 16 GB of HBM, a collective inside the slot-sharded step —
while interpret-mode kernel tests cannot. Nothing runs: results and times
still come only from a chip run (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and test collection must not
depend on whether this process got it.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.elfcore_snn import CONFIG
from repro.core import engine, snn
from repro.serving.adapt import make_chunk_fn

# the serving grid chip_smoke.py drives: ~1024 gesture streams, T=50
N_SLOTS, CHUNK_LEN = 1024, 25
TRAIN_BATCH = 32
HBM_BYTES = 16 * 10**9              # one v5e chip
COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|all-to-all|"
                        r"collective-permute|reduce-scatter)")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding=None):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _chunk_args(cfg, sharding=None):
    """Shapes of ``chunk_fn(params, deltas, state, events, valid,
    adapt_mask)`` at ``cfg`` for the smoke's grid."""
    p = jax.eval_shape(lambda: snn.serving_params(
        snn.init_params(jax.random.PRNGKey(0), cfg), cfg))
    dl = jax.eval_shape(lambda: snn.init_stream_deltas(cfg, N_SLOTS))
    st = jax.eval_shape(lambda: snn.init_stream_state(cfg, N_SLOTS))
    ev = jax.ShapeDtypeStruct((CHUNK_LEN, N_SLOTS, cfg.n_in), jnp.float32)
    va = jax.ShapeDtypeStruct((CHUNK_LEN, N_SLOTS), jnp.bool_)
    am = jax.ShapeDtypeStruct((N_SLOTS,), jnp.bool_)
    return tuple(_shapes(a, sharding) for a in (p, dl, st, ev, va, am))


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


def test_serving_chunk_fn_fits_one_chip(one_chip):
    compiled = make_chunk_fn(CONFIG).lower(
        *_chunk_args(CONFIG, one_chip)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES, \
        compiled.memory_analysis()


def test_training_step_fits_one_chip(one_chip):
    p = jax.eval_shape(lambda: snn.init_params(jax.random.PRNGKey(0), CONFIG))
    st = jax.eval_shape(lambda: snn.init_state(CONFIG, TRAIN_BATCH))
    ev = jax.ShapeDtypeStruct((CONFIG.t_steps, TRAIN_BATCH, CONFIG.n_in),
                              jnp.float32)
    lab = jax.ShapeDtypeStruct((TRAIN_BATCH,), jnp.int32)
    compiled = snn.make_train_fn(CONFIG).lower(
        *(_shapes(a, one_chip) for a in (p, st, ev, lab))).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES, \
        compiled.memory_analysis()


def _nm_spmm(one_chip, rows, k, n, bk, bo):
    from repro.kernels.nm_spmm.ops import nm_spmm_batched
    t = (k // bk) // 2
    return nm_spmm_batched, (
        jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n // bo, t, bk, bo), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n // bo, t), jnp.int32, sharding=one_chip))


def _lif(one_chip, rows, k, n, bk, bo):
    from repro.kernels.lif.ops import lif_step
    fn = lambda v, tr, cur: lif_step(v, tr, cur, alpha=CONFIG.alpha,
                                     beta=CONFIG.beta, theta=CONFIG.theta)
    return fn, tuple(jax.ShapeDtypeStruct((rows, n), jnp.float32,
                                          sharding=one_chip)
                     for _ in range(3))


def _wu_outer(one_chip, rows, k, n, bk, bo):
    from repro.kernels.wu_outer.ops import wu_outer
    t = (k // bk) // 2
    fn = lambda pre, mod, idx, scale: wu_outer(pre, mod, idx, scale,
                                               bk=bk, bo=bo)
    return fn, (
        jax.ShapeDtypeStruct((rows, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((rows, n), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n // bo, t), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))


@pytest.mark.parametrize("kernel", [_nm_spmm, _lif, _wu_outer],
                         ids=["nm_spmm", "lif", "wu_outer"])
def test_kernel_compiles_to_mosaic(one_chip, kernel):
    """The paper's widths (K = N = 512) at 128-aligned N:M blocks: the
    compiled program holds the Mosaic kernel itself."""
    fn, args = kernel(one_chip, rows=256, k=CONFIG.n_in, n=CONFIG.n_hidden,
                      bk=128, bo=128)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_chunk_fn_has_no_collectives(topo):
    """The slot-sharded serving step on a 4-chip mesh is communication-free
    (frozen-topology fleet: the DSST factors, whose slot reduction is the
    one cross-chip sum, are compiled out)."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("slots",))
    fn = make_chunk_fn(CONFIG, mesh=mesh, want_factors=False)
    text = fn.lower(*_chunk_args(CONFIG)).compile().as_text()
    assert not COLLECTIVE.search(text), COLLECTIVE.findall(text)[:5]


def test_pallas_backend_refuses_untileable_spec():
    """The paper's element-granular N:M spec (block = out_tile = 1) cannot
    tile onto the compiled kernels: refused at the seam, not in Mosaic."""
    cfg = dataclasses.replace(CONFIG, backend="pallas")
    assert cfg.spec(cfg.n_in).block == 1
    with pytest.raises(ValueError, match="multiples of 128"):
        engine.make_backend(cfg)
    with pytest.raises(ValueError, match="multiples of 128"):
        snn.make_train_fn(cfg)(
            snn.init_params(jax.random.PRNGKey(0), cfg),
            snn.init_state(cfg, 2),
            jnp.zeros((cfg.t_steps, 2, cfg.n_in)), jnp.zeros((2,), jnp.int32))
