"""Observability of the serving hot path: spans, phase metrics, overlap.

The load-bearing acceptance properties of the obs subsystem:

* **Tracing is free of behavior**: with a live tracer attached, every
  per-stream trajectory — window predictions, final deltas, telemetry
  counters, topology epoch history — is BIT-identical to the untraced
  scheduler (1-device and 8-device subprocess), the chunk step still
  compiles exactly once, and the serving jaxpr is unchanged. Spans wrap
  host phases at already-synchronous points only.
* **Per-phase attribution survives pipelining**: each stage/dispatch/
  retire span carries the grid step that owns the work (a retire span
  recorded inside ``step()`` for step ``t`` belongs to step ``t-1``
  under double buffering — the bug whole-step walls can't see), exactly
  one span of each phase exists per grid step, and the per-phase wall
  sums reconcile with the step+flush walls.
* **Telemetry is bounded**: the step-latency histogram replaces the old
  unbounded list — O(buckets) memory at any stream count/run length,
  percentiles within one bucket width (~10%) of exact.
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.core.dsst import DSSTConfig
from repro.core.snn import (SNNConfig, init_params, init_stream_deltas,
                            init_stream_state)
from repro.obs import Tracer, parse_prometheus_text, prometheus_text
from repro.obs.metrics import LATENCY_BUCKETS_S
from repro.serving import (ReplaySource, StreamScheduler, StreamSession,
                           TopologyService, TopologyServiceConfig)
from repro.serving.telemetry import (STREAM_COUNTER_FAMILIES,
                                     STREAM_GAUGE_FAMILIES, FleetTelemetry)

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


def _drive(params, cfg, depth, tracer=None, n_streams=5, n_slots=3,
           chunk_len=6, topology_every=0):
    svc = None
    if topology_every:
        svc = TopologyService(cfg, TopologyServiceConfig(
            epoch_every=topology_every, merge_top=1))
    sched = StreamScheduler(params, cfg, n_slots=n_slots, chunk_len=chunk_len,
                            topology=svc, pipeline_depth=depth, tracer=tracer)
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


@pytest.fixture(scope="module")
def frozen_runs(params):
    """The same pipelined frozen-fleet workload, tracer off vs on."""
    off = _drive(params, CFG, depth=1)
    on = _drive(params, CFG, depth=1, tracer=Tracer(capacity=65536))
    return off, on


@pytest.fixture(scope="module")
def evolve_runs():
    """The same evolving-fleet workload, tracer off vs on."""
    p = init_params(jax.random.PRNGKey(1), EVOLVE_CFG)
    off = _drive(p, EVOLVE_CFG, depth=1, n_slots=4, topology_every=3)
    on = _drive(p, EVOLVE_CFG, depth=1, n_slots=4, topology_every=3,
                tracer=Tracer(capacity=65536))
    return off, on


# ------------------------------------------------- tracing changes nothing

def test_tracing_on_off_bit_identical(frozen_runs):
    off, on = frozen_runs
    assert off[0].n_compiles == 1 and on[0].n_compiles == 1
    _assert_fleet_identical(off, on)
    assert off[0].tracer.spans() == []          # NULL_TRACER records nothing
    assert on[0].tracer.n_recorded > 0 and on[0].tracer.n_dropped == 0


def test_tracing_on_off_bit_identical_evolving(evolve_runs):
    """With live topology epochs in the loop: same epochs, same evolved
    params/deltas, same trajectories — spans around ``svc.evolve`` change
    nothing about when or how epochs land."""
    off, on = evolve_runs
    va, vb = off[1], on[1]
    assert va.epoch_idx >= 2 and va.epoch_idx == vb.epoch_idx
    assert [(e.grid_step, e.pruned, e.regrown) for e in va.events] == \
           [(e.grid_step, e.pruned, e.regrown) for e in vb.events]
    _assert_fleet_identical(off, on)


def test_serving_jaxpr_unchanged_by_tracer(params):
    """Instrumentation never reaches the jitted computation: the chunk
    fn's jaxpr is identical with and without a tracer attached."""
    def chunk_jaxpr(sched):
        dl = init_stream_deltas(CFG, sched.n_slots)
        st = init_stream_state(CFG, sched.n_slots)
        ev = np.zeros((sched.chunk_len, sched.n_slots, CFG.n_in), np.float32)
        va = np.ones((sched.chunk_len, sched.n_slots), bool)
        am = np.ones(sched.n_slots, bool)
        return str(jax.make_jaxpr(lambda *a: sched.chunk_fn(*a))(
            sched.params, dl, st, ev, va, am))

    s_off = StreamScheduler(params, CFG, n_slots=3, chunk_len=6)
    s_on = StreamScheduler(params, CFG, n_slots=3, chunk_len=6,
                           tracer=Tracer())
    assert chunk_jaxpr(s_off) == chunk_jaxpr(s_on)


def test_tracing_8device_bit_identical(params):
    """Tracer on == tracer off on the 8-device slot-sharded pipelined
    grid, bit for bit (subprocess: XLA pins devices at init)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.snn import SNNConfig, init_params
        from repro.launch.mesh import make_serving_mesh
        from repro.obs import Tracer
        from repro.serving import ReplaySource, StreamScheduler, StreamSession

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
        params = init_params(jax.random.PRNGKey(0), cfg)

        def events(seed, t, rate=0.3):
            r = np.random.default_rng(seed)
            return (r.random((t, cfg.n_in)) < rate).astype(np.float32)

        def drive(tracer):
            sched = StreamScheduler(params, cfg, n_slots=16, chunk_len=5,
                                    mesh=make_serving_mesh(),
                                    pipeline_depth=1, tracer=tracer)
            for sid in range(6):
                sched.submit(StreamSession(
                    sid=sid, source=ReplaySource(events(sid, 2 * cfg.t_steps)),
                    adapt=(sid % 2 == 0)))
            return sched, {s.sid: s for s in sched.run_until_drained()}

        tr = Tracer(capacity=65536)
        s0, d0 = drive(None)
        s1, d1 = drive(tr)
        assert s0.n_compiles == 1 and s1.n_compiles == 1
        for sid in d0:
            assert len(d0[sid].predictions) == len(d1[sid].predictions) == 2
            for a, b in zip(d0[sid].predictions, d1[sid].predictions):
                np.testing.assert_array_equal(a.logits, b.logits)
            np.testing.assert_array_equal(d0[sid].final_deltas,
                                          d1[sid].final_deltas)
        steps = s1.grid.stats["steps"]
        assert len(tr.spans("sched.retire")) == steps > 0
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr


# -------------------------------------------- per-grid-step attribution

def test_span_taxonomy_one_of_each_phase_per_grid_step(frozen_runs):
    """Every grid step owns exactly one stage, one dispatch, and one
    retire span — retires landing in a later ``step()`` (or at flush)
    included — with the owning step in the ``grid_step`` attr."""
    sched, _, _ = frozen_runs[1]
    tr = sched.tracer
    steps = sched.grid.stats["steps"]
    assert steps >= 4
    for name in ("sched.stage", "sched.dispatch", "sched.retire",
                 "sched.poll_sources", "sched.admit", "sched.device_wait"):
        got = sorted(s.attr("grid_step") for s in tr.spans(name))
        assert got == list(range(1, steps + 1)), (name, got)
    assert len(tr.spans("sched.step")) == steps
    # stage span nests poll_sources + admit under it
    by_id = {s.span_id: s for s in tr.spans()}
    for s in tr.spans("sched.poll_sources") + tr.spans("sched.admit"):
        assert by_id[s.parent_id].name == "sched.stage"
    for s in tr.spans("sched.device_wait"):
        assert by_id[s.parent_id].name == "sched.retire"


def test_retire_attributed_to_earlier_grid_step_under_pipelining(frozen_runs):
    """The attribution bugfix: under double buffering, the retire running
    inside ``step()`` for grid step ``t`` belongs to step ``t-1`` — its
    span must say so rather than inherit the enclosing step's number."""
    sched, _, _ = frozen_runs[1]
    tr = sched.tracer
    by_id = {s.span_id: s for s in tr.spans()}
    crossed = 0
    for s in tr.spans("sched.retire"):
        parent = by_id.get(s.parent_id)
        if parent is not None and parent.name == "sched.step":
            assert parent.attr("grid_step") == s.attr("grid_step") + 1
            crossed += 1
        else:
            assert parent is None       # flush-time retire: no step parent
    assert crossed >= 2, "pipeline never overlapped a retire with a step"
    # ...and the in-flight step's results were genuinely hidden behind
    # host work: the aggregate overlap ratio is a real signal, not 0
    tel = sched.telemetry
    assert 0.0 < tel.overlap_ratio() <= 1.0
    assert tel.rollup()["overlap_ratio"] == tel.overlap_ratio()


def test_phase_walls_reconcile_with_step_walls(frozen_runs):
    """stage+dispatch+retire wall sums account for (almost) all of the
    recorded step+flush wall — nothing double counted, nothing lost to
    the pipeline's reordering."""
    tel = frozen_runs[1][0].telemetry
    pp = tel.phase_percentiles()
    assert set(pp) >= {"stage", "dispatch", "retire"}
    phases = sum(pp[k]["total_s"] for k in ("stage", "dispatch", "retire"))
    walls = (tel.registry.get("serving_step_latency_seconds").sum
             + tel.registry.get("serving_flush_seconds_total").value)
    assert phases <= walls + 1e-6, (phases, walls)
    assert phases >= 0.7 * walls, (phases, walls)
    for k in ("stage", "dispatch", "retire"):
        assert pp[k]["p99_ms"] >= pp[k]["p50_ms"] > 0.0


def test_topology_epoch_spans(evolve_runs):
    sched, svc, _ = evolve_runs[1]
    spans = sched.tracer.spans("topology.epoch")
    assert len(spans) == svc.epoch_idx >= 2
    for s, e in zip(spans, svc.events):
        assert s.attr("grid_step") == e.grid_step
        assert s.attr("pruned") == e.pruned
        assert s.attr("regrown") == e.regrown
    assert sched.telemetry.rollup()["topology_epochs"] == svc.epoch_idx


def test_depth2_tracing_parity_and_spans(params, frozen_runs):
    """Deeper queues (frozen fleet): tracing still bit-identical, and
    per-phase spans still land exactly once per grid step."""
    deep = _drive(params, CFG, depth=2, tracer=Tracer(capacity=65536))
    assert deep[0].pipeline.depth == 2
    _assert_fleet_identical(frozen_runs[0], deep)
    steps = deep[0].grid.stats["steps"]
    for name in ("sched.stage", "sched.retire"):
        got = sorted(s.attr("grid_step")
                     for s in deep[0].tracer.spans(name))
        assert got == list(range(1, steps + 1)), (name, got)


# ------------------------------------- host sub-spans and their byte counts

def _nbytes(tree):
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))


def test_sub_spans_nest_under_their_phase(frozen_runs):
    """Admission, dispatch and retire split into named children: one
    ``admit.write`` (the lane-reset program's enqueue) per admission that
    admitted anyone, one transfer and one enqueue per dispatch, one
    deliver, telemetry and snapshot per retire — each the child of its
    phase's span, and delivery before the counter fold."""
    sched, _, _ = frozen_runs[1]
    tr = sched.tracer
    by_id = {s.span_id: s for s in tr.spans()}
    parents = {"admit.write": "sched.admit",
               "dispatch.transfer": "sched.dispatch",
               "dispatch.enqueue": "sched.dispatch",
               "retire.deliver": "sched.retire",
               "retire.telemetry": "sched.retire",
               "retire.snapshot": "sched.retire"}
    for name, parent in parents.items():
        spans = tr.spans(name)
        assert spans, name
        for s in spans:
            assert by_id[s.parent_id].name == parent, (name, s)
    admits = tr.spans("sched.admit")
    assert sum(s.attr("admitted") for s in admits) == 5   # every stream
    busy = [s for s in admits if s.attr("admitted")]
    assert 1 < len(busy) < 5                   # lanes re-admitted, batched
    assert tr.spans("admit.fresh_lane") == []  # no host-built lane
    writes = tr.spans("admit.write")
    assert sorted(w.parent_id for w in writes) \
        == sorted(s.span_id for s in busy)
    assert [w.attr("admitted") for w in writes] \
        == [s.attr("admitted") for s in busy]
    steps = sched.grid.stats["steps"]
    for name in ("dispatch.transfer", "dispatch.enqueue", "retire.deliver",
                 "retire.telemetry", "retire.snapshot"):
        got = sorted(s.attr("grid_step") for s in tr.spans(name))
        assert got == list(range(1, steps + 1)), (name, got)
    for retire in tr.spans("sched.retire"):
        kids = {s.name: s for s in tr.spans()
                if s.parent_id == retire.span_id}
        assert kids["retire.deliver"].t0_s + kids["retire.deliver"].dur_s \
            <= kids["retire.telemetry"].t0_s
        assert kids["retire.telemetry"].t0_s < kids["retire.snapshot"].t0_s


def test_admit_dispatch_and_snapshot_byte_counts(frozen_runs):
    """``bytes_written`` is one fresh lane per admitted session (what the
    reset program scatters into the grid), ``leaves_written`` the grid
    leaves of its one call and ``programs`` 0 or 1; ``h2d_bytes`` is the
    staged buffers; ``d2h_bytes`` the retiring sessions' final deltas —
    and the registry counters add up the same numbers."""
    sched, _, done = frozen_runs[1]
    tr = sched.tracer
    S, C = sched.n_slots, sched.chunk_len
    lane = (init_stream_state(CFG, 1), init_stream_deltas(CFG, 1))
    per_session = _nbytes(lane)
    n_leaves = len(jax.tree_util.tree_leaves(lane))
    admits = tr.spans("sched.admit")
    assert {s.attr("programs") for s in admits} == {0, 1}
    for s in admits:
        n = s.attr("admitted")
        assert s.attr("programs") == int(n > 0), s
        assert s.attr("bytes_written") == n * per_session, s
        assert s.attr("leaves_written") == (n_leaves if n else 0), s
    # packed-bit events, bool valid/mask
    h2d = C * S * -(-CFG.n_in // 8) + C * S + S
    dispatches = tr.spans("sched.dispatch")
    assert {s.attr("h2d_bytes") for s in dispatches} == {h2d}
    lane_delta = _nbytes(init_stream_deltas(CFG, 1))
    snaps = tr.spans("retire.snapshot")
    assert sum(s.attr("retiring") for s in snaps) == len(done) == 5
    for s in snaps:
        assert s.attr("d2h_bytes") == s.attr("retiring") * lane_delta, s
    scrape = parse_prometheus_text(prometheus_text(sched.telemetry.registry))
    assert scrape["serving_admissions_total"] == 5
    assert scrape["serving_admit_bytes_total"] == sum(
        s.attr("bytes_written") for s in admits) == 5 * per_session
    assert scrape["serving_h2d_bytes_total"] == len(dispatches) * h2d
    assert scrape["serving_stream_series"] == len(sched.telemetry.streams) \
        == 5


def test_tracing_8device_bit_identical_with_readmission(params):
    """Tracer on == tracer off on the 8-device slot-sharded pipelined grid
    with more sessions than lanes, so lanes are re-admitted under the
    mesh; the explicit dispatch transfer uses the chunk fn's input
    shardings (no recompile) and its spans and byte counts are there."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.snn import SNNConfig, init_params
        from repro.launch.mesh import make_serving_mesh
        from repro.obs import Tracer
        from repro.serving import ReplaySource, StreamScheduler, StreamSession

        cfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=8)
        params = init_params(jax.random.PRNGKey(0), cfg)

        def events(seed, t, rate=0.3):
            r = np.random.default_rng(seed)
            return (r.random((t, cfg.n_in)) < rate).astype(np.float32)

        def drive(tracer):
            sched = StreamScheduler(params, cfg, n_slots=16, chunk_len=4,
                                    mesh=make_serving_mesh(),
                                    pipeline_depth=1, tracer=tracer)
            for sid in range(40):
                sched.submit(StreamSession(
                    sid=sid,
                    source=ReplaySource(
                        events(sid, (1 + sid % 2) * cfg.t_steps)),
                    adapt=(sid % 2 == 0)))
            return sched, {s.sid: s for s in sched.run_until_drained()}

        tr = Tracer(capacity=65536)
        s0, d0 = drive(None)
        s1, d1 = drive(tr)
        assert s0.n_compiles == 1 and s1.n_compiles == 1
        assert len(d0) == len(d1) == 40
        for sid in d0:
            assert len(d0[sid].predictions) == len(d1[sid].predictions) > 0
            for a, b in zip(d0[sid].predictions, d1[sid].predictions):
                np.testing.assert_array_equal(a.logits, b.logits)
            np.testing.assert_array_equal(d0[sid].final_deltas,
                                          d1[sid].final_deltas)
        steps = s1.grid.stats["steps"]
        assert len(tr.spans("dispatch.transfer")) == steps > 0
        busy = [s for s in tr.spans("sched.admit") if s.attr("admitted")]
        assert sum(s.attr("admitted") for s in busy) == 40
        assert len(tr.spans("admit.write")) == len(busy) < 40
        S = s1.n_slots
        assert {s.attr("h2d_bytes") for s in tr.spans("sched.dispatch")} \
            == {4 * S * (cfg.n_in // 8) + 4 * S + S}    # packed-bit events
        assert s1.deltas.sharding.is_equivalent_to(s0.deltas.sharding, 6)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr


# ------------------------------------------------ named device scopes

CHUNK_SCOPES = ("si_base", "si_delta", "lif", "ossl", "gate", "wu_delta",
                "telemetry", "readout", "window_roll")
TRAIN_SCOPES = ("si_base", "lif", "ossl", "gate", "wu_base", "telemetry",
                "readout", "dsst")


def _scopes_in(hlo_text):
    import re
    names = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        names.update(op_name.split("/"))
    return names


def test_named_scopes_in_compiled_hlo(params):
    """The engine's stages carry their names into the compiled HLO's op
    metadata — what a device profile reads per op — in the serving chunk
    program (``jit_chunk_fn``) and the training step (``jit_step``)."""
    from repro.core.snn import init_state, make_train_fn
    sched = StreamScheduler(params, CFG, n_slots=3, chunk_len=6)
    args = (sched._exec_params, sched.deltas, sched.state,
            np.zeros((6, 3, CFG.n_in), np.float32), np.ones((6, 3), bool),
            np.ones(3, bool))
    hlo = sched.chunk_fn.lower(*args).compile().as_text()
    assert "HloModule jit_chunk_fn" in hlo
    assert set(CHUNK_SCOPES) <= _scopes_in(hlo), \
        set(CHUNK_SCOPES) - _scopes_in(hlo)
    step = make_train_fn(CFG)
    hlo = step.lower(params, init_state(CFG, 4),
                     np.zeros((CFG.t_steps, 4, CFG.n_in), np.float32),
                     np.zeros(4, np.int32)).compile().as_text()
    assert "HloModule jit_step" in hlo
    assert set(TRAIN_SCOPES) <= _scopes_in(hlo), \
        set(TRAIN_SCOPES) - _scopes_in(hlo)


# ------------------------------------------------- telemetry regressions

def test_fleet_telemetry_memory_is_bounded():
    """The ``step_latencies_s`` unbounded-list bug, pinned fixed: 20k
    recorded steps leave the telemetry O(buckets), and the percentile
    view stays within one bucket width of the exact values."""
    tel = FleetTelemetry()
    rng = np.random.default_rng(0)
    vals = np.exp(rng.normal(loc=np.log(2e-3), scale=0.8, size=20_000))
    for v in vals:
        tel.record_step(v)
    assert "step_latencies_s" not in vars(tel)
    assert not any(isinstance(v, list) and len(v) > 100
                   for v in vars(tel).values())
    hist = tel.registry.get("serving_step_latency_seconds").labels()
    assert len(hist.bucket_counts()) == len(LATENCY_BUCKETS_S) + 1
    assert hist.count == 20_000 and tel.steps == 20_000
    lp = tel.latency_percentiles()
    for key, q in (("p50_ms", 50), ("p99_ms", 99)):
        exact = float(np.percentile(vals, q)) * 1e3
        assert abs(lp[key] - exact) / exact < 0.12, (key, lp[key], exact)


def test_topology_epoch_log_bounded_rollup_exact():
    """Regression for the lint-surfaced OBS01 finding: the per-epoch event
    *log* is a bounded recent-events ring, while the rollup reads the
    registry counters — so its totals stay exact past the ring's horizon."""
    tel = FleetTelemetry(max_epoch_events=32)
    n = 500
    for i in range(n):
        tel.record_topology_epoch(grid_step=i, pruned=2, regrown=1,
                                  mask_change=0.01 * (i % 7),
                                  merged_streams=i % 2)
    assert len(tel.topology_epochs) == 32                       # bounded
    assert tel.topology_epochs[-1]["grid_step"] == n - 1        # most recent
    r = tel.topology_rollup()
    assert r["topology_epochs"] == n                            # exact
    assert r["topology_pruned"] == 2 * n
    assert r["topology_regrown"] == n
    assert r["streams_merged"] == sum(i % 2 for i in range(n))
    exact_mean = sum(0.01 * (i % 7) for i in range(n)) / n
    assert r["topology_mask_change_mean"] == pytest.approx(exact_mean)


def test_fleet_telemetry_thread_safe_mutation():
    """Regression for the lint-surfaced OBS02 finding: concurrent sources
    racing on stream() creation and epoch recording lose nothing — one
    counter record per sid, exact epoch totals."""
    import threading

    tel = FleetTelemetry()
    n_threads, per_thread, sids = 8, 50, range(6)
    seen = [[] for _ in range(n_threads)]
    start = threading.Barrier(n_threads)

    def worker(t):
        start.wait()
        for i in range(per_thread):
            seen[t].append(tel.stream(sids[i % len(sids)]))
            tel.record_topology_epoch(grid_step=i, pruned=1, regrown=1,
                                      mask_change=0.0, merged_streams=0)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()

    assert sorted(tel.streams) == list(sids)
    for t in range(n_threads):                  # every thread saw THE record
        for i, rec in enumerate(seen[t]):
            assert rec is tel.streams[sids[i % len(sids)]]
    assert tel.topology_rollup()["topology_epochs"] == n_threads * per_thread


def test_overlap_ratio_accounting():
    tel = FleetTelemetry()
    assert tel.overlap_ratio() == 0.0            # nothing recorded
    assert tel.record_overlap(0.0, 0.01) == 0.0  # serial step: nothing hidden
    assert tel.record_overlap(0.02, 0.01) == pytest.approx(2 / 3)
    assert tel.record_overlap(0.01, 0.0) == 1.0  # fully hidden
    assert tel.overlap_ratio() == pytest.approx(0.03 / 0.05)
    assert tel.registry.get("serving_overlap_ratio").count == 3


def test_prometheus_scrape_of_live_run(frozen_runs):
    """A text scrape of a real run carries the required metric families
    with values that agree with the scheduler's own bookkeeping."""
    sched = frozen_runs[1][0]
    parsed = parse_prometheus_text(prometheus_text(sched.telemetry.registry))
    assert parsed["serving_grid_steps_total"] == sched.grid.stats["steps"]
    assert parsed["serving_step_latency_seconds_count"] == \
        sched.grid.stats["steps"]
    for required in ("serving_overlap_ratio_count",
                     "serving_device_wait_seconds_total",
                     'serving_phase_seconds_count{phase="retire"}',
                     'serving_stream_timesteps_total{sid="0"}',
                     'serving_stream_windows_total{sid="4"}'):
        assert required in parsed, required
    # per-stream counters in the scrape == the in-process view, for every
    # stream of the churned fleet (5 streams through 3 lanes)
    tel = sched.telemetry
    assert sorted(tel.streams) == list(range(5))
    families = {**STREAM_COUNTER_FAMILIES, **STREAM_GAUGE_FAMILIES}
    for sid in tel.streams:
        c = tel.stream(sid)
        assert c.timesteps > 0, sid
        for attr, (name, _) in families.items():
            assert parsed[f'{name}{{sid="{sid}"}}'] == getattr(c, attr), \
                (sid, attr)


def test_swapped_telemetry_splits_the_counters(params, frozen_runs):
    """Swapping ``sched.telemetry`` mid-run, with a step in flight, splits
    each stream's counters between the two sinks at the swap: the two add
    up to the unswapped run's (the loss sums to rounding)."""
    whole = frozen_runs[0][0].telemetry
    sched = StreamScheduler(params, CFG, n_slots=3, chunk_len=6,
                            pipeline_depth=1)
    for sid in range(5):
        sched.submit(StreamSession(
            sid=sid,
            source=ReplaySource(_events(sid, (3 + sid % 2) * CFG.t_steps,
                                        rate=0.25 + 0.03 * sid),
                                chunk_len=7),
            adapt=(sid % 2 == 0)))
    while not sched.retired:        # swap once a lane has been re-admitted
        sched.step()
    sched.step()
    assert len(sched.pipeline) == 1
    assert {s.sid for s in sched.grid.occupant if s} & {3, 4}
    first, sched.telemetry = sched.telemetry, FleetTelemetry()
    sched.run_until_drained()
    second = sched.telemetry
    assert set(first.streams) | set(second.streams) == set(whole.streams)
    for sid in whole.streams:
        a, b, want = first.streams.get(sid), second.streams.get(sid), \
            whole.stream(sid)
        for attr in (*STREAM_COUNTER_FAMILIES, *STREAM_GAUGE_FAMILIES):
            got = sum(getattr(c, attr) for c in (a, b) if c is not None)
            if attr in STREAM_COUNTER_FAMILIES:
                assert got == getattr(want, attr), (sid, attr)
            else:
                assert got == pytest.approx(getattr(want, attr), rel=1e-12)


def test_retire_telemetry_counts_folded_lanes(frozen_runs):
    """``retire.telemetry`` carries ``lanes``: the lanes its step staged,
    which the step's dispatch counted too, including the draining steps
    that ran with lanes empty."""
    sched, _, _ = frozen_runs[1]
    tr = sched.tracer
    staged = {s.attr("grid_step"): s.attr("lanes")
              for s in tr.spans("sched.dispatch")}
    folds = {s.attr("grid_step"): s.attr("lanes")
             for s in tr.spans("retire.telemetry")}
    assert folds == staged
    assert max(folds.values()) == sched.n_slots
    assert min(folds.values()) < sched.n_slots


# ------------------------------------------------------- overhead guard

def test_tracing_overhead_guard(params):
    """Tracing must stay out of the hot path's way: best-of-5 drained-
    fleet walls with a live tracer within 25% of untraced (the quick
    serving bench pins the tighter <5%-events/s budget; this guard keeps
    gross regressions — a sync, a per-step allocation storm — out)."""
    def build(tracer):
        sched = StreamScheduler(params, CFG, n_slots=4, chunk_len=6,
                                pipeline_depth=1, tracer=tracer)
        sched.submit(StreamSession(                      # warmup: compile
            sid=999, source=ReplaySource(_events(99, CFG.t_steps))))
        sched.run_until_drained()
        return sched

    def wave(sched, base_sid):
        for k in range(6):
            sched.submit(StreamSession(
                sid=base_sid + k,
                source=ReplaySource(_events(k, 2 * CFG.t_steps), chunk_len=7),
                adapt=(k % 2 == 0)))
        t0 = time.perf_counter()
        sched.run_until_drained()
        return time.perf_counter() - t0

    off, on = build(None), build(Tracer(capacity=65536))
    walls_off, walls_on = [], []
    for rep in range(5):                   # interleaved: fair to both
        walls_off.append(wave(off, 1000 + 100 * rep))
        walls_on.append(wave(on, 5000 + 100 * rep))
    assert min(walls_on) <= min(walls_off) * 1.25, (walls_on, walls_off)
    assert on.tracer.n_recorded > 0


# ------------------------------------------------- continuous batcher

def test_batcher_spans_and_parity():
    import repro.configs as C
    from repro.launch.batching import ContinuousBatcher, Request
    from repro.models import transformer as T
    cfg = C.get_reduced("phi3_medium_14b")
    p = T.init_params(jax.random.PRNGKey(0), cfg)

    def drive(tracer):
        b = ContinuousBatcher(p, cfg, n_slots=2, max_seq=32, tracer=tracer)
        b.submit(Request(rid=0, prompt=[1, 2, 3], max_new=3))
        return b, b.run_until_drained()

    tr = Tracer()
    b_on, done_on = drive(tr)
    _, done_off = drive(None)
    assert done_on[0].out == done_off[0].out         # tracing-free behavior
    steps = b_on.grid.stats["steps"]
    admits, decodes = tr.spans("batch.admit"), tr.spans("batch.decode_step")
    assert len(admits) == len(decodes) == steps >= 4
    # the first step replays prompt (prefill), later steps decode
    assert decodes[0].attr("prefill_slots") == 1
    assert decodes[0].attr("decode_slots") == 0
    assert decodes[-1].attr("decode_slots") == 1
    assert [d.attr("grid_step") for d in decodes] == \
        list(range(1, steps + 1))
