"""Unit tests for the jaxpr contract analyzer (repro.analysis).

Each contract must statically catch its planted violation on a toy
function — a psum under shard_map, a slot-axis reduction, a dense-mask
constvar, a factor carry in a scan, an f64 promotion, a retracing
entrypoint — and pass on the clean twin. The registry test then runs every
real entrypoint's contract set end to end (the CI static-analysis suite).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro import analysis

S = 4     # toy slot count — distinct from every other extent used below


class _Cfg:
    """Duck-typed stand-in for SNNConfig (what the contract factories
    actually read)."""
    n_layers = 2
    n_hidden = 8
    layer_fanins = (16, 8)     # k_max = 16 != n_hidden


# ------------------------------------------------------- no_collectives

def _slot_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("slots",))


def test_no_collectives_catches_planted_psum():
    mesh = _slot_mesh()

    def planted(x):
        def body(x):
            return jax.lax.psum(x, "slots")
        return shard_map(body, mesh=mesh, in_specs=P("slots"),
                         out_specs=P())(x)

    r = analysis.check(planted, (jnp.zeros((S, 3)),),
                       [analysis.no_collectives()])
    assert not r.ok
    assert any("psum" in v.message and "shard_map" in v.message
               for v in r.violations)
    with pytest.raises(analysis.ContractViolationError, match="psum"):
        r.raise_if_violations()


def test_no_collectives_passes_clean_shard_map():
    mesh = _slot_mesh()

    def clean(x):
        def body(x):
            return x * 2.0
        return shard_map(body, mesh=mesh, in_specs=P("slots"),
                         out_specs=P("slots"))(x)

    analysis.check(clean, (jnp.zeros((S, 3)),),
                   [analysis.no_collectives()]).raise_if_violations()


def test_no_collectives_axis_filter():
    mesh = _slot_mesh()

    def planted(x):
        def body(x):
            return jax.lax.psum(x, "slots")
        return shard_map(body, mesh=mesh, in_specs=P("slots"),
                         out_specs=P())(x)

    args = (jnp.zeros((S, 3)),)
    assert not analysis.check(planted, args,
                              [analysis.no_collectives(axis="slots")]).ok
    # a collective over a *different* named axis is out of scope
    assert analysis.check(planted, args,
                          [analysis.no_collectives(axis="model")]).ok


# ------------------------------------------------------- slot_separable

def test_slot_separable_catches_planted_slot_sum():
    def planted(x):                      # x: [S, N]
        return {"kept": x * 2.0, "mean": x.sum(0)}

    r = analysis.check(planted, (jnp.zeros((S, 8)),),
                       [analysis.slot_separable(S)])
    assert not r.ok
    assert len(r.violations) == 1
    assert "mean" in r.violations[0].message
    assert "lost the slot axis" in r.violations[0].message


def test_slot_separable_exempt_and_second_dim():
    def fn(x):                           # slot axis allowed at dim 0 or 1
        return {"a": x, "b": jnp.moveaxis(x, 0, 1), "mean": x.sum(0)}

    args = (jnp.zeros((S, 8)),)
    assert not analysis.check(fn, args, [analysis.slot_separable(S)]).ok
    analysis.check(
        fn, args,
        [analysis.slot_separable(S, exempt=("mean",))]).raise_if_violations()


# ----------------------------------------------- mask_free / dense leaves

def test_mask_free_catches_planted_dense_mask_constvar():
    cfg = _Cfg()
    k_max = max(cfg.layer_fanins)
    mask = np.ones((cfg.n_layers, k_max, cfg.n_hidden), np.float32)

    def planted(x):
        return (jnp.asarray(mask) * x).sum()

    r = analysis.check(planted, (jnp.zeros(()),), [analysis.mask_free(cfg)])
    assert not r.ok
    assert any("dense layout" in v.message for v in r.violations)

    def clean(x):
        return x * 2.0

    analysis.check(clean, (jnp.zeros(()),),
                   [analysis.mask_free(cfg)]).raise_if_violations()


def test_no_dense_deltas_catches_both_layouts():
    cfg = _Cfg()
    k_max = max(cfg.layer_fanins)
    contracts = [analysis.no_dense_deltas(cfg, S)]

    def slot_leading(x):
        return x + jnp.zeros((S, cfg.n_layers, k_max, cfg.n_hidden))

    def layer_leading(x):
        return x + jnp.zeros((cfg.n_layers, S, k_max, cfg.n_hidden))

    assert not analysis.check(slot_leading, (jnp.zeros(()),), contracts).ok
    assert not analysis.check(layer_leading, (jnp.zeros(()),), contracts).ok


# ------------------------------------------------------ no_factor_carries

def _scan_with_carries(n_lsn, n_lsk, cfg, C):
    """A toy chunk scan carrying ``n_lsn`` [L,S,N] and ``n_lsk`` [L,S,Kmax]
    f32 arrays."""
    L, N, k_max = cfg.n_layers, cfg.n_hidden, max(cfg.layer_fanins)

    def fn(xs):
        def body(c, x):
            return tuple(a + x for a in c), x
        c0 = (tuple(jnp.zeros((L, S, N)) for _ in range(n_lsn))
              + tuple(jnp.zeros((L, S, k_max)) for _ in range(n_lsk)))
        return jax.lax.scan(body, c0, xs)
    return fn


def test_no_factor_carries_catches_planted_accumulators():
    cfg, C = _Cfg(), 5
    contracts = [analysis.no_factor_carries(cfg, S, chunk_len=C)]
    args = (jnp.zeros((C, 1, 1, 1)),)

    # 4 [L,S,N] carries = the LayerState leaves — allowed
    analysis.check(_scan_with_carries(4, 0, cfg, C), args,
                   contracts).raise_if_violations()
    # a 5th [L,S,N] (the post_mag accumulator) — caught
    assert not analysis.check(_scan_with_carries(5, 0, cfg, C), args,
                              contracts).ok
    # any [L,S,Kmax] (the pre_mag accumulator; k_max != N here) — caught
    assert not analysis.check(_scan_with_carries(0, 1, cfg, C), args,
                              contracts).ok


def test_no_factor_carries_chunk_len_scoping():
    cfg, C = _Cfg(), 5
    # a scan of a DIFFERENT length may carry what it likes
    r = analysis.check(
        _scan_with_carries(5, 1, cfg, C), (jnp.zeros((C, 1, 1, 1)),),
        [analysis.no_factor_carries(cfg, S, chunk_len=C + 1)])
    assert r.ok


# ------------------------------------------------------ dtype_discipline

def test_dtype_discipline_catches_f64():
    def planted(x):
        return x.astype(jnp.float64) + np.float64(1.0)

    with jax.enable_x64(True):
        r = analysis.check(planted, (jnp.zeros((3,), jnp.float32),),
                           [analysis.dtype_discipline()])
    assert not r.ok
    assert any("float64" in v.message for v in r.violations)

    def clean(x):
        return x + 1.0

    analysis.check(clean, (jnp.zeros((3,), jnp.float32),),
                   [analysis.dtype_discipline()]).raise_if_violations()


# -------------------------------------------------------- compile_count

def _counted_fn(retrace_every_call):
    traces = {"n": 0}

    def body(x):
        traces["n"] += 1
        return x + 1.0
    stable = jax.jit(body)

    def fn(x):
        if retrace_every_call:
            # a fresh closure per call defeats jit's cache → retrace
            def fresh(y):
                traces["n"] += 1
                return y + 1.0
            return jax.jit(fresh)(x)
        return stable(x)
    fn.n_traces = lambda: traces["n"]
    return fn


def test_compile_count_passes_stable_entrypoint():
    analysis.check(_counted_fn(False), (jnp.zeros((2,)),),
                   [analysis.compile_count()]).raise_if_violations()


def test_compile_count_catches_retracing():
    r = analysis.check(_counted_fn(True), (jnp.zeros((2,)),),
                       [analysis.compile_count()])
    assert not r.ok
    assert "retracing" in r.violations[0].message


def test_compile_count_requires_trace_counter():
    r = analysis.check(lambda x: x, (jnp.zeros((2,)),),
                       [analysis.compile_count()])
    assert not r.ok and "n_traces" in r.violations[0].message


# --------------------------------------------- the shared trace-time assert

def _fake_chunk_trees(C, S, L, N, want_factors, break_leaf=None):
    layers = {"v": jnp.zeros((L, S, N)), "tr": jnp.zeros((L, S, N))}
    x_tr = jnp.zeros((S, 6))
    ss_mean = jnp.zeros((L, S))
    t_w = jnp.zeros((S,))
    samp = jnp.zeros((S,))
    dls = jnp.zeros((L, S, 3, N))
    acc = ((jnp.zeros((L, S, 5)), jnp.zeros((L, S, N)))
           if want_factors else ())
    outs = {"spk": jnp.zeros((C, S, N))}
    if break_leaf == "out":
        outs["spk"] = jnp.zeros((C, N))          # slot axis reduced away
    if break_leaf == "carry":
        ss_mean = jnp.zeros((L,))
    return (layers, x_tr, ss_mean, t_w, samp, dls, *acc), outs


@pytest.mark.parametrize("want_factors", [False, True])
def test_chunk_carry_assert_accepts_separable_trees(want_factors):
    C, L, N = 6, 2, 8
    carry, outs = _fake_chunk_trees(C, S, L, N, want_factors)
    analysis.assert_chunk_carry_slot_separable(
        carry, outs, C=C, S=S, n_layers=L, want_factors=want_factors)


@pytest.mark.parametrize("break_leaf", ["out", "carry"])
def test_chunk_carry_assert_catches_dropped_slot_axis(break_leaf):
    C, L, N = 6, 2, 8
    carry, outs = _fake_chunk_trees(C, S, L, N, True, break_leaf=break_leaf)
    with pytest.raises(AssertionError):
        analysis.assert_chunk_carry_slot_separable(
            carry, outs, C=C, S=S, n_layers=L, want_factors=True)


def test_engine_assert_is_the_shared_one():
    """Satellite: engine._assert_slot_separable wraps the analyzer —
    same AssertionError, same shape-bearing message."""
    from repro.core import engine, snn

    cfg = snn.SNNConfig(n_in=16, n_hidden=8, n_layers=2, n_out=4, t_steps=4)
    C, L, N = 6, 2, cfg.n_hidden
    carry, outs = _fake_chunk_trees(C, S, L, N, False, break_leaf="out")
    with pytest.raises(AssertionError) as ei:
        engine._assert_slot_separable(carry, outs, C, S, cfg, False)
    assert str((C, N)) in str(ei.value)          # the offending shape


# --------------------------------------------------------- report / walkers

def test_report_formatting_and_walkers():
    def fn(xs):
        def body(c, x):
            return c + x, c
        return jax.lax.scan(body, jnp.zeros(()), xs)

    r = analysis.check(fn, (jnp.zeros((3,)),), [analysis.no_collectives()],
                       name="toy.scan")
    assert r.ok and "toy.scan" in str(r) and "OK" in str(r)

    closed = jax.make_jaxpr(fn)(jnp.zeros((3,)))
    names = [e.primitive.name for e, _ in analysis.iter_eqns(closed)]
    assert "scan" in names
    roles = {role for _, role in analysis.all_avals(closed)}
    assert "input" in roles and "eqn-out" in roles


# ------------------------------------------------------------ the registry

def test_registry_every_entrypoint_passes():
    """Acceptance: every registered real entrypoint (compact and dense
    layouts, sharded and unsharded, factors on and off) passes its
    contract set on a small config."""
    from repro.analysis import registry

    reports = registry.check_all()
    assert set(reports) == set(registry.names())
    assert {"serving.chunk_fn[compact,factors]",
            "serving.chunk_fn[compact,frozen]", "serving.chunk_fn[dense]",
            "serving.chunk_fn[sharded]",
            "serving.chunk_fn[compact,factors,packed]",
            "serving.chunk_fn[compact,frozen,packed]",
            "serving.chunk_fn[sharded,packed]", "snn.run_chunk[compact]",
            "snn.run_chunk[dense]", "launch.decode_step"} <= set(reports)
    for name, r in reports.items():
        assert r.ok, f"{name}:\n{r}"

    s = registry.summary(reports)
    assert s["ok"] and s["violations"] == 0
    assert s["contracts"] >= 20
    assert s["entrypoints"] == sorted(reports)
