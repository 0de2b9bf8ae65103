"""Pipelined serving hot path: staging overlap + compiled-out DSST factors.

Two acceptance properties of the hot-path tentpole:

* **Pipelining changes *when* host work happens, never *what* the device
  computes**: with double-buffered staging (``pipeline_depth=1``) every
  per-stream trajectory — window predictions, final deltas, telemetry
  counters, and (for evolving fleets) the whole topology epoch history —
  is BIT-identical to the serial scheduler, on one device and on an
  8-device slot-sharded mesh, and the chunk step still compiles once.
* **``want_factors=False`` really compiles the DSST factor machinery
  out**: the chunk metrics carry no factor leaves, the chunk scan's carry
  holds no factor accumulator (asserted on the jaxpr), and the stream
  dynamics are bit-identical either way.

Plus the primitive underneath the cheap evolving-fleet path:
``engine.ordered_slot_sum``'s reduction tree is a function of S alone, so
the device-side factor reduction is sharding-independent.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.core.dsst import DSSTConfig
from repro.core.snn import (SNNConfig, init_params, init_stream_deltas,
                            init_stream_state, run_chunk)
from repro.serving import (AdaptConfig, ReplaySource, StagingPipeline,
                           StreamScheduler, StreamSession, TopologyService,
                           TopologyServiceConfig, make_chunk_fn)

CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
EVOLVE_CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=12,
                       dsst=DSSTConfig(period=4, prune_frac=0.5))

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _events(seed, t, rate=0.3):
    rng = np.random.default_rng(seed)
    return (rng.random((t, CFG.n_in)) < rate).astype(np.float32)


def _drive(params, cfg, depth, n_streams=5, n_slots=3, chunk_len=6,
           topology_every=0):
    svc = None
    if topology_every:
        svc = TopologyService(cfg, TopologyServiceConfig(
            epoch_every=topology_every, merge_top=1))
    sched = StreamScheduler(params, cfg, n_slots=n_slots, chunk_len=chunk_len,
                            topology=svc, pipeline_depth=depth)
    for sid in range(n_streams):
        sched.submit(StreamSession(
            sid=sid,
            source=ReplaySource(_events(sid, (3 + sid % 2) * cfg.t_steps,
                                        rate=0.25 + 0.03 * sid),
                                chunk_len=7),
            adapt=(sid % 2 == 0)))
    done = {s.sid: s for s in sched.run_until_drained()}
    return sched, svc, done


def _assert_fleet_identical(a, b):
    """(sched, svc, done) pairs: bit-identical per-stream outcomes."""
    sa, va, da = a
    sb, vb, db = b
    assert set(da) == set(db)
    for sid in da:
        pa, pb = da[sid].predictions, db[sid].predictions
        assert len(pa) == len(pb) > 0, (sid, len(pa), len(pb))
        for x, y in zip(pa, pb):
            np.testing.assert_array_equal(x.logits, y.logits)
        np.testing.assert_array_equal(da[sid].final_deltas,
                                      db[sid].final_deltas)
        ca, cb = sa.telemetry.stream(sid), sb.telemetry.stream(sid)
        for f in ("timesteps", "events_in", "sop_forward", "sop_wu",
                  "sop_wu_offered", "gate_opened", "gate_offered",
                  "windows", "local_loss"):
            assert getattr(ca, f) == getattr(cb, f), (sid, f)
    for x, y in zip(jax.tree_util.tree_leaves(sa.params),
                    jax.tree_util.tree_leaves(sb.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    np.testing.assert_array_equal(np.asarray(sa.deltas), np.asarray(sb.deltas))


# --------------------------------------------------------- pipeline parity

def test_pipeline_on_off_bit_exact(params):
    """Double-buffered staging == serial phases, bit for bit: predictions,
    final deltas, per-stream counters — with oversubscription (5 streams on
    3 slots) so admit/retire lane recycling crosses the pipeline boundary.
    One compile each (the pipeline adds no shapes)."""
    serial = _drive(params, CFG, depth=0)
    piped = _drive(params, CFG, depth=1)
    assert serial[0].n_compiles == 1 and piped[0].n_compiles == 1
    _assert_fleet_identical(serial, piped)
    # pipeline actually drained: nothing left in flight after run
    assert piped[0].drained and len(piped[0].pipeline) == 0


def test_pipeline_parity_with_live_topology_epochs(params):
    """Evolving fleet: epochs land between the same grid steps, fold the
    same hot lanes, and produce the same evolved (params, deltas) under the
    pipeline as serially — the epoch-vs-dispatch ordering contract."""
    p = init_params(jax.random.PRNGKey(1), EVOLVE_CFG)
    serial = _drive(p, EVOLVE_CFG, depth=0, n_slots=4, topology_every=3)
    piped = _drive(p, EVOLVE_CFG, depth=1, n_slots=4, topology_every=3)
    va, vb = serial[1], piped[1]
    assert va.epoch_idx >= 2, "workload too short: no epochs ran"
    assert va.epoch_idx == vb.epoch_idx
    assert [(e.grid_step, e.pruned, e.regrown, e.merged_slots)
            for e in va.events] == \
           [(e.grid_step, e.pruned, e.regrown, e.merged_slots)
            for e in vb.events]
    _assert_fleet_identical(serial, piped)
    # a live topology service clamps deeper queues back to depth 1
    deep = StreamScheduler(p, EVOLVE_CFG, n_slots=4, pipeline_depth=3,
                           topology=TopologyService(EVOLVE_CFG))
    assert deep.pipeline.depth == 1


def test_pipeline_depth_two_frozen_fleet_parity(params):
    """Without a topology service deeper queues are allowed and still
    bit-identical — bookkeeping just lands later."""
    serial = _drive(params, CFG, depth=0)
    deep = _drive(params, CFG, depth=2)
    assert deep[0].pipeline.depth == 2
    _assert_fleet_identical(serial, deep)


def test_pipeline_8device_sharded_parity(params):
    """Pipelined + slot-sharded over 8 devices == serial 1-device grid,
    bit for bit, one compile each (subprocess: XLA pins devices at init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.snn import SNNConfig, init_params
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import ReplaySource, StreamScheduler, StreamSession

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
        params = init_params(jax.random.PRNGKey(0), cfg)

        def events(seed, t, rate=0.3):
            r = np.random.default_rng(seed)
            return (r.random((t, cfg.n_in)) < rate).astype(np.float32)

        def drive(mesh, depth):
            sched = StreamScheduler(params, cfg, n_slots=16, chunk_len=5,
                                    mesh=mesh, pipeline_depth=depth)
            for sid in range(6):
                sched.submit(StreamSession(
                    sid=sid, source=ReplaySource(events(sid, 2 * cfg.t_steps)),
                    adapt=(sid % 2 == 0)))
            return sched, {s.sid: s for s in sched.run_until_drained()}

        s1, d1 = drive(None, 0)
        s8, d8 = drive(make_serving_mesh(), 1)
        assert s1.n_compiles == 1 and s8.n_compiles == 1
        for sid in d1:
            assert len(d1[sid].predictions) == len(d8[sid].predictions) == 2
            for a, b in zip(d1[sid].predictions, d8[sid].predictions):
                np.testing.assert_array_equal(a.logits, b.logits)
            np.testing.assert_array_equal(d1[sid].final_deltas,
                                          d8[sid].final_deltas)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr


def test_staging_pipeline_bounds():
    pl = StagingPipeline(depth=0)
    with pytest.raises(RuntimeError, match="synchronous"):
        pl.push(object())
    with pytest.raises(ValueError, match="depth"):
        StagingPipeline(depth=-1)
    pl = StagingPipeline(depth=1)
    assert not pl.full and len(pl) == 0
    pl.push("a")
    assert pl.full
    with pytest.raises(RuntimeError, match="full"):
        pl.push("b")
    assert pl.pop() == "a" and len(pl) == 0


# ----------------------------------------------------- factor compile-out

def test_want_factors_off_metrics_and_dynamics(params):
    """want_factors=False: metrics carry no factor leaves; deltas/state are
    bit-identical to the factor-bearing step (the factors are telemetry,
    never dynamics)."""
    st = init_stream_state(CFG, 2)
    dl = init_stream_deltas(CFG, 2)
    ev = _events(40, 10)[:, None, :].repeat(2, 1)
    va = np.ones((10, 2), bool)
    amask = np.ones(2, bool)
    fn_on = make_chunk_fn(CFG, AdaptConfig(), want_factors=True)
    fn_off = make_chunk_fn(CFG, AdaptConfig(), want_factors=False)
    d1, s1, m1 = fn_on(params, dl, st, ev, va, amask)
    d0, s0, m0 = fn_off(params, dl, st, ev, va, amask)
    assert m0.pre_mag is None and m0.post_mag is None
    assert m1.pre_mag.shape == (CFG.n_layers, CFG.n_in)       # slot-reduced
    assert m1.post_mag.shape == (CFG.n_layers, CFG.n_hidden)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
    for a, b in zip(jax.tree_util.tree_leaves(s1),
                    jax.tree_util.tree_leaves(s0)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fn_off.want_factors is False and fn_on.want_factors is True


def test_want_factors_false_compiles_accumulators_out_of_scan():
    """The acceptance assert: with want_factors=False the chunk scan's
    jaxpr contains NO factor accumulator in its carry — not a zeroed one,
    none. Since the static-analysis PR the scan-carry walk lives in the
    shared ``no_factor_carries`` contract; the with-factors trace doubles
    as its planted positive (both the unique [L, S, Kmax] pre accumulator
    and the extra [L, S, N] post accumulator must be called out)."""
    from repro import analysis

    cfg = SNNConfig(n_in=48, n_hidden=16, n_layers=2, n_out=4, t_steps=8)
    C, S = 5, 3
    params = init_params(jax.random.PRNGKey(2), cfg)
    st = init_stream_state(cfg, S)
    dl = init_stream_deltas(cfg, S)
    ev = jnp.zeros((C, S, cfg.n_in))
    va = jnp.ones((C, S), bool)

    def fn(want_factors):
        def f(p, d, s, e, v):
            return run_chunk(p, d, s, e, v, cfg, want_factors=want_factors)
        return f

    contracts = [analysis.no_factor_carries(cfg, S, chunk_len=C)]
    args = (params, dl, st, ev, va)
    analysis.check(fn(False), args, contracts).raise_if_violations()

    on = analysis.check(fn(True), args, contracts)
    assert not on.ok
    msgs = " ".join(v.message for v in on.violations)
    L, k_max = cfg.n_layers, max(cfg.layer_fanins)
    assert str([L, S, k_max]) in msgs            # pre accumulator caught
    assert str([L, S, cfg.n_hidden]) in msgs     # extra post acc caught


def test_live_topology_requires_factors(params):
    svc = TopologyService(EVOLVE_CFG)
    assert not svc.frozen
    p = init_params(jax.random.PRNGKey(3), EVOLVE_CFG)
    with pytest.raises(ValueError, match="factors"):
        StreamScheduler(p, EVOLVE_CFG, n_slots=2, topology=svc,
                        want_factors=False)
    # inferred default: factors on with a live service, off without
    assert StreamScheduler(p, EVOLVE_CFG, n_slots=2,
                           topology=svc).want_factors is True
    assert StreamScheduler(params, CFG, n_slots=2).want_factors is False


# --------------------------------------------------- ordered slot reduction

def test_ordered_slot_sum_fixed_tree():
    """The reduction tree is a function of S alone: equals an explicit
    adjacent-pair reference bit-for-bit, for odd and even S, and is
    invariant to how the array is split — each power-of-two contiguous
    block reduced on its own, then the block sums by the same tree, is the
    same bits (the sharded-parity mechanism, testable without devices)."""
    rng = np.random.default_rng(0)

    def ref(a):
        while a.shape[0] > 1:
            e = a.shape[0] // 2 * 2
            p = a[0:e:2] + a[1:e:2]
            a = p if e == a.shape[0] else np.concatenate([p, a[e:]], 0)
        return a[0]

    for S in (1, 2, 3, 7, 8, 16):
        x = (rng.standard_normal((S, 4, 5)).astype(np.float32) * 1e3)
        got = np.asarray(engine.ordered_slot_sum(jnp.asarray(x)))
        np.testing.assert_array_equal(got, ref(x))
        # and under jit (the form the chunk fn actually runs)
        jitted = np.asarray(jax.jit(engine.ordered_slot_sum)(jnp.asarray(x)))
        np.testing.assert_array_equal(jitted, ref(x))
    # split into D contiguous shards of S/D (a power of two): the shard
    # subtrees, combined by the tree's top levels, are the whole tree
    for S, D in ((16, 4), (16, 8), (32, 2), (64, 4)):
        x = (rng.standard_normal((S, 4, 5)).astype(np.float32) * 1e3)
        parts = jnp.stack([engine.ordered_slot_sum(jnp.asarray(b))
                           for b in np.split(x, D)])
        np.testing.assert_array_equal(
            np.asarray(engine.ordered_slot_sum(parts)), ref(x))
