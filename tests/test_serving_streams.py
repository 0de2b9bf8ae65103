"""Event-stream serving runtime: isolation, lifecycle, gating, telemetry.

The load-bearing property is per-slot separability: a stream multiplexed
into a busy slot grid must see bit-for-bit (up to fp32 batching effects)
the same spikes, traces, and weight deltas as when it runs alone. Everything
else — admit/retire reuse, gated adaptation, telemetry — layers on that.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.snn import (SNNConfig, init_params, init_stream_deltas,
                            init_stream_state, run_chunk)
from repro.data.events import make_task
from repro.launch.batching import SlotGrid
from repro.serving import (AdaptConfig, ReplaySource, SessionStatus,
                           StreamScheduler, StreamSession, TaskStreamSource,
                           delta_norms, make_chunk_fn, read_lane, write_lane)

CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _events(seed, t, rate=0.25):
    rng = np.random.default_rng(seed)
    return (rng.random((t, CFG.n_in)) < rate).astype(np.float32)


def _run_lane(params, ev, n_slots, lane, chunk_len=6, others=()):
    """Feed ``ev`` into ``lane`` of an ``n_slots`` grid; ``others`` are
    (lane, events) streams fed concurrently. Returns (state, deltas)."""
    st = init_stream_state(CFG, n_slots)
    dl = init_stream_deltas(CFG, n_slots)
    cursors = {lane: [ev, 0]}
    for ln, oe in others:
        cursors[ln] = [oe, 0]
    while any(c < e.shape[0] for e, c in cursors.values()):
        events = np.zeros((chunk_len, n_slots, CFG.n_in), np.float32)
        valid = np.zeros((chunk_len, n_slots), bool)
        for ln, cur in cursors.items():
            e, c = cur
            n = min(chunk_len, e.shape[0] - c)
            if n > 0:
                events[:n, ln] = e[c:c + n]
                valid[:n, ln] = True
                cur[1] = c + n
        dl, st, _ = run_chunk(params, dl, st, jnp.asarray(events),
                              jnp.asarray(valid), CFG)
    return st, dl


# ------------------------------------------------------------- isolation

def test_interleaved_matches_solo(params):
    """Two interleaved streams == each run alone (traces, CC slot, deltas,
    per-stream gate thresholds) to fp32 tolerance."""
    ev_a, ev_b = _events(1, 40), _events(2, 40, rate=0.35)
    st_a, dl_a = _run_lane(params, ev_a, n_slots=1, lane=0)
    st_b, dl_b = _run_lane(params, ev_b, n_slots=1, lane=0)
    st2, dl2 = _run_lane(params, ev_a, n_slots=3, lane=0,
                         others=[(2, ev_b)])    # lane 1 stays idle

    for l in range(CFG.n_layers):
        np.testing.assert_allclose(st2.layers.tr[0, l], st_a.layers.tr[0, l],
                                   atol=1e-5)
        np.testing.assert_allclose(st2.layers.tr_cc[0, l],
                                   st_a.layers.tr_cc[0, l], atol=1e-5)
        np.testing.assert_allclose(st2.layers.tr[2, l], st_b.layers.tr[0, l],
                                   atol=1e-5)
        np.testing.assert_allclose(dl2[0, l], dl_a[0, l], atol=1e-5)
        np.testing.assert_allclose(dl2[2, l], dl_b[0, l], atol=1e-5)
    np.testing.assert_allclose(st2.ss_mean[0], st_a.ss_mean[0], atol=1e-6)
    np.testing.assert_allclose(st2.ss_mean[2], st_b.ss_mean[0], atol=1e-6)
    # the idle lane never moved
    assert float(jnp.abs(st2.layers.tr[1]).max()) == 0.0
    assert float(delta_norms(dl2)[1]) == 0.0


def test_chunk_boundaries_do_not_matter(params):
    """The same stream cut into different ragged chunkings ends identically."""
    ev = _events(3, 37)
    st1, dl1 = _run_lane(params, ev, n_slots=1, lane=0, chunk_len=6)
    st2, dl2 = _run_lane(params, ev, n_slots=1, lane=0, chunk_len=11)
    for l in range(CFG.n_layers):
        np.testing.assert_allclose(st1.layers.tr[0, l], st2.layers.tr[0, l],
                                   atol=1e-5)
        np.testing.assert_allclose(dl1[0, l], dl2[0, l], atol=1e-5)
    assert int(st1.sample_idx[0]) == int(st2.sample_idx[0]) == 37 // CFG.t_steps


def test_all_invalid_chunk_is_exact_noop(params):
    st = init_stream_state(CFG, 2)
    dl = init_stream_deltas(CFG, 2)
    ev = jnp.asarray(_events(4, 5))[:, None, :].repeat(2, 1)
    valid = jnp.zeros((5, 2), bool)
    dl2, st2, m = run_chunk(params, dl, st, ev, valid, CFG)
    for a, b in zip(jax.tree_util.tree_leaves((st, dl)),
                    jax.tree_util.tree_leaves((st2, dl2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(m.sop_forward.sum()) == 0.0
    assert float(m.sop_wu_offered.sum()) == 0.0
    assert float(m.steps.sum()) == 0.0


def test_scheduler_interleaved_matches_solo(params):
    """End-to-end through the scheduler: window predictions of a stream are
    unaffected by a neighbor stream sharing the grid."""
    ev = _events(5, 2 * CFG.t_steps)
    def preds(extra_stream):
        sched = StreamScheduler(params, CFG, n_slots=2, chunk_len=5)
        sched.submit(StreamSession(sid=0, source=ReplaySource(ev, chunk_len=7)))
        if extra_stream:
            sched.submit(StreamSession(
                sid=1, source=ReplaySource(_events(6, 50, 0.4), chunk_len=9)))
        done = {s.sid: s for s in sched.run_until_drained()}
        return done[0].predictions
    solo, inter = preds(False), preds(True)
    assert len(solo) == len(inter) == 2
    for a, b in zip(solo, inter):
        np.testing.assert_allclose(a.logits, b.logits, atol=1e-5)


# ------------------------------------------------------------- lifecycle

def test_admit_retire_slot_reuse(params):
    """More streams than slots: lanes are recycled and every stream ends
    RETIRED with its predictions and a final-delta snapshot."""
    task = make_task("gesture", n_in=CFG.n_in, t_steps=CFG.t_steps)
    sched = StreamScheduler(params, CFG, n_slots=2, chunk_len=8)
    for sid in range(5):
        sched.submit(StreamSession(
            sid=sid, source=TaskStreamSource(task, n_windows=1, seed=sid)))
    done = sched.run_until_drained()
    assert len(done) == 5
    assert sched.grid.stats["admitted"] == 5
    assert sched.grid.stats["retired"] == 5
    assert sched.grid.drained
    for s in done:
        assert s.status is SessionStatus.RETIRED and s.slot is None
        assert len(s.predictions) == 1
        assert s.final_deltas is not None
    assert 0.0 < sched.utilization <= 1.0


def test_slot_grid_helper():
    g: SlotGrid = SlotGrid(2)
    for i in range(3):
        g.submit(i)
    admitted = g.admit()
    assert [s for s, _ in admitted] == [0, 1] and g.free_slots() == []
    assert g.retire(0) == 0
    assert g.admit() == [(0, 2)]
    g.tick()
    assert not g.drained and g.stats["slot_busy"] == 2
    g.retire(0), g.retire(1)
    assert g.drained


# ------------------------------------------------------------- adaptation

def test_silent_stream_never_updates(params):
    """IA gate: an all-silent stream pays zero WU energy and keeps delta 0."""
    sched = StreamScheduler(params, CFG, n_slots=1, chunk_len=8)
    silent = np.zeros((3 * CFG.t_steps, CFG.n_in), np.float32)
    sched.submit(StreamSession(sid=0, source=ReplaySource(silent)))
    sched.run_until_drained()
    c = sched.telemetry.stream(0)
    assert c.sop_wu == 0.0 and c.gate_opened == 0.0
    assert float(np.abs(np.concatenate(
        [d.ravel() for d in sched.retired[0].final_deltas])).max()) == 0.0
    # but the gate was *offered* decisions and the stream was stepped
    assert c.gate_offered > 0 and c.timesteps == silent.shape[0]


def test_active_stream_adapts_and_frozen_does_not(params):
    """SS/IA gating opens for novel activity; a ``adapt=False`` session keeps
    its lane's delta frozen while state still tracks the stream."""
    ev = _events(7, 3 * CFG.t_steps, rate=0.3)
    sched = StreamScheduler(params, CFG, n_slots=2, chunk_len=8)
    sched.submit(StreamSession(sid=0, source=ReplaySource(ev.copy())))
    sched.submit(StreamSession(sid=1, source=ReplaySource(ev.copy()),
                               adapt=False))
    done = {s.sid: s for s in sched.run_until_drained()}
    n0 = float(np.sqrt(sum((d ** 2).sum() for d in done[0].final_deltas)))
    n1 = float(np.sqrt(sum((d ** 2).sum() for d in done[1].final_deltas)))
    assert n0 > 0.0, "gated OSSL never fired on an active stream"
    assert n1 == 0.0, "frozen session's delta moved"
    assert sched.telemetry.stream(1).sop_wu == 0.0
    # the frozen lane still produced the same number of window predictions
    assert len(done[0].predictions) == len(done[1].predictions) == 3


def test_idle_lane_delta_untouched_by_decay(params):
    """Regression: delta decay/clip used to run on every adaptive lane every
    grid step, idle or not — an empty slot slowly bled its delta toward 0.
    Hygiene must only touch lanes with valid timesteps this chunk."""
    fn = make_chunk_fn(CFG, AdaptConfig(delta_decay=0.9, delta_clip=0.05))
    st = init_stream_state(CFG, 2)
    # both lanes carry accumulated adaptation
    dl = jnp.full_like(init_stream_deltas(CFG, 2), 0.04)
    before = np.asarray(dl).copy()
    ev = jnp.asarray(_events(11, 5, rate=0.4))[:, None, :].repeat(2, 1)
    valid = np.zeros((5, 2), bool)
    valid[:, 0] = True    # lane 1 idle in every chunk
    amask = np.ones(2, bool)
    for _ in range(4):
        dl, st, _ = fn(params, dl, st, ev, jnp.asarray(valid), amask)
    np.testing.assert_array_equal(np.asarray(dl[1]), before[1])
    # the active lane's hygiene still ran: decay bled its parked delta
    assert float(np.abs(np.asarray(dl[0])).max()) < 0.04
    assert not np.array_equal(np.asarray(dl[0]), before[0])


def test_frozen_lane_offered_counters_masked(params):
    """Regression: sop_wu/gate_opened were zeroed for adapt=False lanes but
    the *offered* counters were not, so a frozen stream reported a fake
    100% wu_skip_rate. Frozen lanes must offer nothing too."""
    fn = make_chunk_fn(CFG)
    st = init_stream_state(CFG, 2)
    dl = init_stream_deltas(CFG, 2)
    ev = jnp.asarray(_events(12, CFG.t_steps, rate=0.4))[:, None, :].repeat(2, 1)
    valid = jnp.ones((CFG.t_steps, 2), bool)
    amask = np.array([True, False])
    dl, st, m = fn(params, dl, st, ev, valid, amask)
    assert float(m.sop_wu_offered[0]) > 0
    assert float(m.sop_wu_offered[1]) == 0.0
    assert float(m.gate_offered[1].sum()) == 0.0
    # scheduler-level: a frozen stream's skip rate reads 0 (nothing offered),
    # not 100% (everything "skipped")
    sched = StreamScheduler(params, CFG, n_slots=1, chunk_len=8)
    sched.submit(StreamSession(
        sid=0, source=ReplaySource(_events(13, 2 * CFG.t_steps, 0.4)),
        adapt=False))
    sched.run_until_drained()
    c = sched.telemetry.stream(0)
    assert c.sop_wu_offered == 0.0 and c.wu_skip_rate == 0.0
    assert c.timesteps == 2 * CFG.t_steps    # the stream was still served


def test_gate_skips_repetitive_stream(params):
    """SS gate: after per-stream threshold calibration, a stream repeating
    the same window pattern skips far more WUs than a varied one."""
    rng = np.random.default_rng(0)
    window = (rng.random((CFG.t_steps, CFG.n_in)) < 0.3).astype(np.float32)
    repetitive = np.concatenate([window] * 8, axis=0)
    varied = _events(9, 8 * CFG.t_steps, rate=0.3)
    sched = StreamScheduler(params, CFG, n_slots=2, chunk_len=8)
    sched.submit(StreamSession(sid=0, source=ReplaySource(repetitive)))
    sched.submit(StreamSession(sid=1, source=ReplaySource(varied)))
    sched.run_until_drained()
    rep = sched.telemetry.stream(0)
    var = sched.telemetry.stream(1)
    assert rep.wu_skip_rate > var.wu_skip_rate, (
        rep.wu_skip_rate, var.wu_skip_rate)


# ------------------------------------------------------------- telemetry

def test_telemetry_monotone_and_separable(params):
    ev = _events(10, 2 * CFG.t_steps, rate=0.3)
    sched = StreamScheduler(params, CFG, n_slots=2, chunk_len=4)
    sched.submit(StreamSession(sid=0, source=ReplaySource(ev)))
    sched.submit(StreamSession(
        sid=1, source=ReplaySource(np.zeros((40, CFG.n_in), np.float32))))
    prev = {}
    while not sched.grid.drained:
        sched.step()
        for sid, c in sched.telemetry.streams.items():
            snap = (c.timesteps, c.sop_forward, c.sop_wu, c.sop_wu_offered,
                    c.gate_offered, c.events_in)
            if sid in prev:
                assert all(b >= a for a, b in zip(prev[sid], snap)), sid
            prev[sid] = snap
    c0, c1 = sched.telemetry.stream(0), sched.telemetry.stream(1)
    # separable: the silent stream consumed zero input events and forward SOPs
    assert c1.events_in == 0.0 and c1.sop_forward == 0.0
    assert c0.events_in == float(ev.sum()) and c0.sop_forward > 0
    # fleet rollup is the sum of the per-stream counters
    r = sched.telemetry.rollup()
    assert r["events_in"] == c0.events_in + c1.events_in
    assert r["timesteps"] == c0.timesteps + c1.timesteps
    assert r["windows"] == c0.windows + c1.windows
    per = sched.telemetry.per_stream()
    assert [p["sid"] for p in per] == [0, 1]
    assert per[1]["power_uW"] < per[0]["power_uW"]   # silent slot is cheaper


def test_zero_recompilation_across_traffic_patterns(params):
    """Ragged chunks, admits, retires, idle slots: still one compilation."""
    task = make_task("shd_kws", n_in=CFG.n_in, t_steps=CFG.t_steps)
    sched = StreamScheduler(params, CFG, n_slots=4, chunk_len=8)
    for sid in range(7):
        sched.submit(StreamSession(
            sid=sid, source=TaskStreamSource(task, n_windows=1, seed=sid)))
    done = sched.run_until_drained()
    assert len(done) == 7
    assert sched.n_compiles == 1


# ------------------------------------------------------------- lane surgery

def test_pop_chunk_empty_is_column_shaped():
    """Regression: an empty pop returned shape (0, 0), a broadcast footgun
    for callers that concatenate or index columns. Width (the packed
    ``ceil(n_in/8)`` bytes a timestep) comes from the first pushed chunk,
    or from ``n_in`` stamped at construction/submit."""
    width = -(-CFG.n_in // 8)
    sess = StreamSession(sid=0, n_in=CFG.n_in)
    assert sess.pop_chunk(4).shape == (0, width)
    sess2 = StreamSession(sid=1)
    sess2.push_events(np.zeros((3, CFG.n_in), np.float32))
    assert sess2.pop_chunk(8).shape == (3, width)
    assert sess2.pop_chunk(8).shape == (0, width)        # drained: width kept
    with pytest.raises(ValueError, match="width"):
        sess2.push_events(np.zeros((2, CFG.n_in + 1), np.float32))
    # the scheduler stamps n_in at submit, so even a never-pushed session
    # pops well-shaped empties
    params = init_params(jax.random.PRNGKey(1), CFG)
    sched = StreamScheduler(params, CFG, n_slots=1)
    fresh = StreamSession(sid=2)
    sched.submit(fresh)
    assert fresh.n_in == CFG.n_in
    assert fresh.pop_chunk(4).shape == (0, width)
    # a session whose learned width disagrees with the grid fails at submit,
    # not mid-step with a half-mutated grid
    wrong = StreamSession(sid=3)
    wrong.push_events(np.zeros((2, CFG.n_in + 1), np.float32))
    with pytest.raises(ValueError, match="n_in"):
        sched.submit(wrong)


def test_write_read_lane_roundtrip():
    st = init_stream_state(CFG, 3)
    one = init_stream_state(CFG, 1)
    one = one._replace(x_tr=one.x_tr + 7.0)
    st2 = write_lane(st, one, 1)
    back = read_lane(st2, 1)
    np.testing.assert_array_equal(np.asarray(back.x_tr), np.asarray(one.x_tr))
    # other lanes untouched
    assert float(jnp.abs(st2.x_tr[0]).max()) == 0.0
    assert float(jnp.abs(st2.x_tr[2]).max()) == 0.0
