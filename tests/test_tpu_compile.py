"""Compiles of the SNN main path for a described TPU v5e (no chip needed).

The TPU compiler ships with libtpu and compiles for a chip that is
described rather than attached, so these tests refuse here what the chip's
compiler would refuse — a program that does not fit 16 GB of HBM, a
collective inside the slot-sharded step, a copy where the program should
update in place. Nothing runs: results and times
still come only from a chip run (``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process at a time may load libtpu, and test collection must not
depend on whether this process got it.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.elfcore_snn import CONFIG
from repro.core import snn
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


def _events(cfg, n_slots, packed=False, sharding=None):
    """The chunk fn's events: float32 spikes, or the packed uint8 bit rows
    the scheduler stages."""
    if packed:
        return jax.ShapeDtypeStruct((CHUNK_LEN, n_slots, -(-cfg.n_in // 8)),
                                    jnp.uint8, sharding=sharding)
    return jax.ShapeDtypeStruct((CHUNK_LEN, n_slots, cfg.n_in), jnp.float32,
                                sharding=sharding)


def _chunk_args(cfg, sharding=None, packed=False):
    """Shapes of ``chunk_fn(params, deltas, state, events, valid,
    adapt_mask)`` at ``cfg`` for the smoke's grid."""
    p = jax.eval_shape(lambda: snn.serving_params(
        snn.init_params(jax.random.PRNGKey(0), cfg), cfg))
    dl = jax.eval_shape(lambda: snn.init_stream_deltas(cfg, N_SLOTS))
    st = jax.eval_shape(lambda: snn.init_stream_state(cfg, N_SLOTS))
    ev = _events(cfg, N_SLOTS, packed)
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


def test_packed_chunk_fn_fits_one_chip(one_chip):
    """The program the scheduler runs, fed packed spike rows: its input
    shrinks by the float32 grid less the packed one (1/32 of it), and the
    grid widened inside the program takes that saving back: the program's
    bytes stay within 0.1 % of the float32 program's."""
    f32 = make_chunk_fn(CONFIG).lower(
        *_chunk_args(CONFIG, one_chip)).compile().memory_analysis()
    m = make_chunk_fn(CONFIG).lower(
        *_chunk_args(CONFIG, one_chip, packed=True)).compile(
        ).memory_analysis()
    grid = CHUNK_LEN * N_SLOTS * CONFIG.n_in * 4
    assert f32.argument_size_in_bytes - m.argument_size_in_bytes \
        == grid - grid // 32, (f32, m)
    total = lambda a: (a.argument_size_in_bytes + a.output_size_in_bytes
                       + a.temp_size_in_bytes)
    assert total(m) <= total(f32) + total(f32) // 1000, (f32, m)


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


def test_sharded_chunk_fn_has_no_collectives(topo):
    """The slot-sharded serving step on a 4-chip mesh is communication-free
    (frozen-topology fleet: the DSST factors, whose slot reduction is the
    one cross-chip sum, are compiled out)."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("slots",))
    fn = make_chunk_fn(CONFIG, mesh=mesh, want_factors=False)
    text = fn.lower(*_chunk_args(CONFIG)).compile().as_text()
    assert not COLLECTIVE.search(text), COLLECTIVE.findall(text)[:5]


def _collective_dims(text: str):
    """Every dimension of every shape on the HLO lines that hold a
    collective (its result and its operands)."""
    dims = set()
    for line in text.splitlines():
        if COLLECTIVE.search(line):
            for shape in re.findall(r"\b[a-z]+\d*\[([\d,]*)\]", line):
                dims.update(int(d) for d in shape.split(",") if d)
    return dims


LIVE_SLOTS = 4096                   # the live four-chip cell's fleet


def _live_chunk_fn(topo, packed):
    """The live four-chip cell's chunk program, compiled."""
    from repro.launch import sharding
    mesh = Mesh(np.asarray(topo.devices[:4]), ("slots",))
    fn = make_chunk_fn(CONFIG, mesh=mesh, want_factors=True)
    rep, slot = sharding.replicated(mesh), sharding.slot_sharding(mesh)
    p = jax.eval_shape(lambda: snn.serving_params(
        snn.init_params(jax.random.PRNGKey(0), CONFIG), CONFIG))
    dl = jax.eval_shape(lambda: snn.init_stream_deltas(CONFIG, LIVE_SLOTS))
    st = jax.eval_shape(lambda: snn.init_stream_state(CONFIG, LIVE_SLOTS))
    col = sharding.slot_sharding(mesh, 1)
    return fn.lower(
        _shapes(p, rep), _shapes(dl, slot), _shapes(st, slot),
        _events(CONFIG, LIVE_SLOTS, packed, col),
        jax.ShapeDtypeStruct((CHUNK_LEN, LIVE_SLOTS), jnp.bool_,
                             sharding=col),
        jax.ShapeDtypeStruct((LIVE_SLOTS,), jnp.bool_,
                             sharding=slot)).compile()


def test_live_chunk_fn_moves_no_slot_shard(topo):
    """With live DSST on the 4-chip slot mesh, each chip reduces its own
    slots' factors inside the step: the only collective left combines the
    four [1, L, ·] partials, and none carries the slot extent (S or
    S/4)."""
    compiled = _live_chunk_fn(topo, packed=False)
    dims = _collective_dims(compiled.as_text())
    assert not dims & {LIVE_SLOTS, LIVE_SLOTS // 4}, sorted(dims)
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_live_packed_chunk_fn_moves_no_slot_shard(topo):
    """The same with the packed spike rows the scheduler stages: each chip
    widens its own shard, so no collective appears for the events, and
    the program needs no more device bytes than the float32 one."""
    compiled = _live_chunk_fn(topo, packed=True)
    dims = _collective_dims(compiled.as_text())
    assert not dims & {LIVE_SLOTS, LIVE_SLOTS // 4}, sorted(dims)
    f32 = _live_chunk_fn(topo, packed=False)
    assert 0 < _device_bytes(compiled) <= _device_bytes(f32)


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "mesh4"])
def test_epoch_program_is_in_place(topo, chips):
    """The live epoch program at the paper's widths: the donated delta
    grid is its output (aliased) and its temporaries stay far under one
    chip's shard of the grid — no second grid, on one chip or four."""
    from repro.launch import sharding
    from repro.serving.topology_service import (TopologyServiceConfig,
                                                make_epoch_program)
    S = N_SLOTS * chips
    mesh = (Mesh(np.asarray(topo.devices[:chips]), ("slots",))
            if chips > 1 else None)
    rep = sharding.replicated(mesh) if mesh else SingleDeviceSharding(
        topo.devices[0])
    slot = sharding.slot_sharding(mesh) if mesh else rep
    fn = make_epoch_program(CONFIG, TopologyServiceConfig(merge_top=2),
                            mesh=mesh)
    p = jax.eval_shape(lambda: snn.init_params(jax.random.PRNGKey(0),
                                               CONFIG))
    dl = jax.eval_shape(lambda: snn.init_stream_deltas(CONFIG, S))
    fac = jax.ShapeDtypeStruct((CONFIG.n_layers, CONFIG.n_hidden),
                               jnp.float32, sharding=rep)
    compiled = fn.lower(
        _shapes(p, rep), _shapes(dl, slot), fac, fac,
        jax.ShapeDtypeStruct((S,), jnp.bool_, sharding=slot),
        (6, 6)).compile()
    shard = dl.size * dl.dtype.itemsize // chips
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= shard, m
    assert m.temp_size_in_bytes < shard // 50, m


@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "mesh4"])
def test_lane_reset_is_in_place(topo, chips):
    """Admission's lane reset at the smoke's grid: the donated grids are
    the outputs (aliased), the program holds no second copy of them, and
    on the 4-chip slot mesh it needs no collective."""
    from repro.launch import sharding
    from repro.serving.session import make_reset_lanes
    mesh = Mesh(np.asarray(topo.devices[:chips]), ("slots",))
    st = jax.eval_shape(lambda: snn.init_stream_state(CONFIG, N_SLOTS))
    dl = jax.eval_shape(lambda: snn.init_stream_deltas(CONFIG, N_SLOTS))
    st_sh = sharding.stream_shardings(st, mesh)
    dl_sh = sharding.slot_sharding(mesh)
    fn = make_reset_lanes(CONFIG, True, st_sh, dl_sh)
    compiled = fn.lower(
        jax.tree_util.tree_map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            st, st_sh),
        _shapes(dl, dl_sh),
        jax.ShapeDtypeStruct((N_SLOTS,), jnp.int32,
                             sharding=sharding.replicated(mesh))).compile()
    grid = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves((st, dl))) // chips
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= grid, m
    assert m.temp_size_in_bytes < grid // 100, m
    text = compiled.as_text()
    assert not COLLECTIVE.search(text), COLLECTIVE.findall(text)[:5]
