"""Tiered QoS serving: async ingestion, per-tier grids, adaptive depth.

The load-bearing property for every feature here is *bit-identity*:
async ingestion, QoS tiers and the depth autopilot change when host work
happens and how the fleet is laid out — never what the device computes
for any stream. Each section pins one leg:

* ingest on == ingest off, chunk for chunk, at every tick (the worker
  replays the virtual clock exactly);
* an exhausted source with a queued tail chunk still retires exactly
  once, with the tail fed (the EOS-exactly-once regression);
* queue depth never exceeds the configured capacity (backpressure parks
  the producer instead of growing memory);
* the autopilot never oscillates on a noisy signal, stays in bounds, and
  an adaptive run is bit-identical to every fixed depth it visited;
* a tiered fleet matches per-tier single-grid references, and 8-device
  sharded tiered/adaptive runs match 1-device serial references.
"""
import os
import subprocess
import sys
import textwrap
import time

import jax
import numpy as np
import pytest

from repro.core.snn import SNNConfig, init_params
from repro.data.events import make_task
from repro.serving import (AERStreamSource, ArrivalConfig, AutopilotConfig,
                           DepthAutopilot, IngestConfig, IngestWorker,
                           ReplaySource, SessionStatus, StreamScheduler,
                           StreamSession, TaskStreamSource, TierConfig)
from repro.serving.staging import InFlight, StagedChunk, StagingPipeline

CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# jittered arrivals: ragged chunks, bursty gaps — the traffic shape the
# async-ingestion A/B is about
_JITTER = ArrivalConfig(min_chunk=3, max_chunk=13, mean_gap_s=0.004,
                        start_jitter_s=0.02)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _events(seed, t, rate=0.25):
    rng = np.random.default_rng(seed)
    return (rng.random((t, CFG.n_in)) < rate).astype(np.float32)


def _mixed_sessions(n=4):
    """A fleet mixing replay, jittered-task and AER-packed sources."""
    task = make_task("gesture", n_in=CFG.n_in, t_steps=CFG.t_steps)
    out = []
    for sid in range(n):
        if sid % 3 == 0:
            src = ReplaySource(_events(sid, (2 + sid % 2) * CFG.t_steps,
                                       rate=0.25 + 0.03 * sid), chunk_len=7)
        elif sid % 3 == 1:
            src = TaskStreamSource(task, n_windows=2, seed=sid,
                                   arrival=_JITTER)
        else:
            src = AERStreamSource(task, n_windows=2, seed=sid,
                                  arrival=_JITTER)
        out.append(StreamSession(sid=sid, source=src, adapt=(sid % 2 == 0)))
    return out


def _run_fleet(params, sessions, **kw):
    sched = StreamScheduler(params, CFG, **kw)
    for s in sessions:
        sched.submit(s)
    done = {s.sid: s for s in sched.run_until_drained()}
    sched.close()
    return done, sched


def _assert_fleet_identical(a, b):
    """Bit-for-bit per-stream identity: predictions, final deltas, fed
    timesteps. (Exact equality, not allclose — these paths must not
    change device arithmetic at all.)"""
    assert set(a) == set(b)
    for sid in a:
        sa, sb = a[sid], b[sid]
        assert sa.timesteps_fed == sb.timesteps_fed, sid
        assert len(sa.predictions) == len(sb.predictions), sid
        for pa, pb in zip(sa.predictions, sb.predictions):
            np.testing.assert_array_equal(pa.logits, pb.logits)
        np.testing.assert_array_equal(sa.final_deltas, sb.final_deltas)


# ------------------------------------------------- async ingestion parity

def test_ingest_bit_identical_to_serial(params):
    """The whole point of the determinism contract: moving source polling
    to the worker thread changes nothing a stream observes."""
    ref, _ = _run_fleet(params, _mixed_sessions(), n_slots=2, chunk_len=6)
    got, sched = _run_fleet(params, _mixed_sessions(), n_slots=2,
                            chunk_len=6, ingest=True)
    _assert_fleet_identical(ref, got)
    st = sched.ingest.stats()
    assert st["chunks_queued"] > 0          # the worker actually worked
    assert st["attached"] == 0              # every stream detached at retire
    assert sched.telemetry.tier_rollup()["ingest_chunks"] > 0


def test_ingest_with_pipelining_bit_identical(params):
    ref, _ = _run_fleet(params, _mixed_sessions(), n_slots=2, chunk_len=6)
    got, _ = _run_fleet(params, _mixed_sessions(), n_slots=2, chunk_len=6,
                        ingest=True, pipeline_depth=2)
    _assert_fleet_identical(ref, got)


def test_aer_source_poll_identical_to_dense():
    """AER pack/densify round trip is exact: an AERStreamSource releases
    the same chunks at the same virtual times as its dense twin."""
    task = make_task("nav_cue", n_in=CFG.n_in, t_steps=CFG.t_steps)
    dense = TaskStreamSource(task, n_windows=3, seed=5, arrival=_JITTER)
    aer = AERStreamSource(task, n_windows=3, seed=5, arrival=_JITTER)
    assert aer.n_timesteps == dense.n_timesteps
    np.testing.assert_array_equal(aer.labels, dense.labels)
    now = 0.0
    while not dense.exhausted:
        now += 0.002
        a, d = aer.poll(now), dense.poll(now)
        assert len(a) == len(d)
        for ca, cd in zip(a, d):
            np.testing.assert_array_equal(ca, cd)
    assert aer.exhausted


# ------------------------------------------------------- EOS exactly once

def test_eos_exactly_once_with_lookahead(params):
    """Lookahead polling flips ``source.exhausted`` while the tail chunk
    still sits in the worker queue. The session must NOT retire until the
    tail is fed — and must retire exactly once when it is (the lost-tail
    / double-retire regression)."""
    cfg = IngestConfig(capacity_chunks=256, lookahead_ticks=128)
    ref, _ = _run_fleet(params, _mixed_sessions(6), n_slots=2, chunk_len=6)
    got, sched = _run_fleet(params, _mixed_sessions(6), n_slots=2,
                            chunk_len=6, ingest=cfg)
    _assert_fleet_identical(ref, got)
    # exactly-once: every session retired once, with every source timestep
    sids = [s.sid for s in sched.retired]
    assert sorted(sids) == sorted(set(sids)) == sorted(got)
    for s in sched.retired:
        assert s.status is SessionStatus.RETIRED
        assert s.timesteps_fed == s.source.n_timesteps, (
            f"stream {s.sid} lost its queued tail")
        assert s._pending == [] and s._ingest is None


def test_session_exhausted_consults_ingest_queue(params):
    """Unit view of the same hole: a session whose source is done but
    whose tail chunk is still queued in the worker reports exhausted
    only after the drain releases it."""
    w = IngestWorker(0.002, IngestConfig(capacity_chunks=8,
                                         lookahead_ticks=64))
    sess = StreamSession(sid=0, source=ReplaySource(_events(0, 24),
                                                    chunk_len=8))
    w.attach(sess)
    # steal-poll far ahead without releasing: drain(0) publishes tick 0,
    # then the worker (or a big drain) races ahead of the grid
    deadline = time.monotonic() + 5.0
    while not sess.source.exhausted and time.monotonic() < deadline:
        time.sleep(0.001)
    assert sess.source.exhausted          # lookahead outran the grid
    assert w.has_pending(0)
    assert not sess.exhausted             # the EOS fix: queued tail counts
    w.drain(64)                           # release everything
    assert not w.has_pending(0)
    assert sess.pending_timesteps() == 24
    sess.pop_chunk(24)
    assert sess.exhausted
    w.detach(sess)
    w.stop()


def test_detach_with_undrained_chunks_raises():
    w = IngestWorker(0.002, IngestConfig(lookahead_ticks=64))
    sess = StreamSession(sid=0, source=ReplaySource(_events(1, 24),
                                                    chunk_len=8))
    w.attach(sess)
    deadline = time.monotonic() + 5.0
    while not w.has_pending(0) and time.monotonic() < deadline:
        time.sleep(0.001)
    with pytest.raises(RuntimeError, match="undrained"):
        w.detach(sess)
    w.stop()


# ------------------------------------------------------------ backpressure

def test_bounded_queue_backpressure():
    """With no drain ever published, the worker polls each stream at most
    ``capacity_chunks`` deep and parks — the queue high-water mark is the
    obs bounded-container invariant."""
    cap = 3
    # lookahead >> capacity so capacity, not lookahead, is the binding cap
    w = IngestWorker(0.002, IngestConfig(capacity_chunks=cap,
                                         lookahead_ticks=100))
    sess = StreamSession(sid=0, source=ReplaySource(_events(2, 400),
                                                    chunk_len=8))
    w.attach(sess)
    deadline = time.monotonic() + 5.0
    while w.stats()["chunks_queued"] < cap and time.monotonic() < deadline:
        time.sleep(0.001)
    time.sleep(0.02)                      # give it rope to overshoot
    st = w.stats()
    assert st["queue_peak"] == cap, st
    assert st["chunks_queued"] == cap, "parked stream kept being polled"
    # a drain frees capacity and un-parks the producer
    pushed, peak = w.drain(1)
    assert pushed == 1 and peak == cap
    deadline = time.monotonic() + 5.0
    while w.stats()["chunks_queued"] < cap + 1 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert w.stats()["queue_peak"] == cap
    w.stop()


def test_backpressure_invariant_via_telemetry(params):
    """Fleet-level: the exported high-water gauge respects the cap."""
    cap = 2
    _, sched = _run_fleet(params, _mixed_sessions(), n_slots=2, chunk_len=6,
                          ingest=IngestConfig(capacity_chunks=cap,
                                              lookahead_ticks=16))
    roll = sched.telemetry.tier_rollup()
    assert 0 < roll["ingest_queue_peak"] <= cap
    fam = sched.telemetry.registry.get("serving_ingest_queue_peak_chunks")
    assert fam is not None and fam.value <= cap


def test_ingest_config_validation():
    with pytest.raises(ValueError):
        IngestConfig(capacity_chunks=0)
    with pytest.raises(ValueError):
        IngestConfig(lookahead_ticks=0)
    w = IngestWorker(0.002)
    s = StreamSession(sid=7, source=ReplaySource(_events(3, 8)))
    w.attach(s)
    with pytest.raises(ValueError, match="already attached"):
        w.attach(s)
    w.drain(4)
    w.detach(s)
    w.stop()


# ------------------------------------------------------------- autopilot

def test_autopilot_hysteresis_no_oscillation():
    """A noisy overlap signal alternating far above/below the deadband
    must not flap the depth: changes are spaced >= hold_steps apart, and
    the deadband absorbs the EMA's excursions."""
    ap = DepthAutopilot(AutopilotConfig(max_depth=3, decide_every=1,
                                        hold_steps=10, warmup_obs=1,
                                        deepen_above=0.6, relax_below=0.05))
    depth, changes = 1, []
    ap.note_depth(0, depth)
    for step in range(1, 200):
        ap.observe(0.9 if step % 2 else 0.1)   # violently noisy signal
        new = ap.decide(step, depth)
        if new != depth:
            changes.append(step)
            ap.note_depth(step, new)
            depth = new
    for a, b in zip(changes, changes[1:]):
        assert b - a >= 10, f"changes {a}->{b} inside the hold window"
    # EMA of a 0.9/0.1 alternation sits mid-deadband -> nearly no changes
    assert len(changes) <= 2, changes


def test_autopilot_bounds_and_probe():
    cfg = AutopilotConfig(max_depth=2, decide_every=1, hold_steps=1,
                          warmup_obs=1, deepen_above=0.5, relax_below=0.2)
    ap = DepthAutopilot(cfg)
    ap.note_depth(0, 0)
    assert ap.decide(1, 0) == 0            # warming up: no observations yet
    ap.observe(0.0)
    depth = ap.decide(2, 0)
    assert depth == 1                      # serial probes regardless of EMA
    ap.note_depth(2, depth)
    for step in range(3, 40):              # saturating high signal
        ap.observe(1.0)
        depth = ap.decide(step, depth)
        ap.note_depth(step, depth)
    assert depth == cfg.max_depth          # bounded above
    for step in range(40, 120):            # saturating low signal
        ap.observe(0.0)
        depth = ap.decide(step, depth)
        ap.note_depth(step, depth)
    assert depth == cfg.min_pipelined_depth  # floored, never back to 0
    assert ap.depths_visited() == (0, 1, 2)


def test_autopilot_config_validation():
    with pytest.raises(ValueError):
        AutopilotConfig(min_pipelined_depth=3, max_depth=2)
    with pytest.raises(ValueError):
        AutopilotConfig(deepen_above=0.2, relax_below=0.5)
    with pytest.raises(ValueError):
        AutopilotConfig(ema_alpha=0.0)


def test_set_depth_only_at_drain_safe_boundary():
    p = StagingPipeline(depth=1)
    staged = StagedChunk(events=None, valid=None, adapt_mask=None, lanes=[],
                         retiring=[], merge_slots=(), fed={})
    p.push(InFlight(staged=staged, deltas=None, metrics=None, grid_step=1))
    with pytest.raises(RuntimeError, match="flush"):
        p.set_depth(2)
    p.pop()
    p.set_depth(2)                         # empty pipeline: fine
    assert p.depth == 2
    with pytest.raises(ValueError):
        p.set_depth(-1)


def test_adaptive_bit_identical_to_every_fixed_depth(params):
    """The acceptance property: an adaptive run that visited depths
    {0, 1, 2} is per-stream bit-identical to fixed-depth references at
    every one of those depths."""
    ap_cfg = AutopilotConfig(max_depth=2, decide_every=1, hold_steps=2,
                             warmup_obs=1, deepen_above=0.0,
                             relax_below=0.0)   # deepen on any overlap > 0
    sessions = lambda: _mixed_sessions(6)
    got, sched = _run_fleet(params, sessions(), n_slots=2, chunk_len=6,
                            ingest=True, autopilot=ap_cfg)
    visited = sched.autopilot.depths_visited()
    assert len(visited) > 1, "autopilot never moved — test proves nothing"
    assert sched.telemetry.tier_rollup()["depth_changes"] >= 1
    assert list(sched.autopilot.timeline)[0] == (0, 0)
    for depth in visited:
        ref, _ = _run_fleet(params, sessions(), n_slots=2, chunk_len=6,
                            pipeline_depth=depth)
        _assert_fleet_identical(ref, got)


def test_autopilot_clamped_by_topology_service(params):
    """A live topology service caps drain-safe depth at 1; the autopilot
    must inherit that clamp, not fight it."""
    from repro.core.dsst import DSSTConfig
    from repro.serving import TopologyService, TopologyServiceConfig

    tcfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=12,
                     dsst=DSSTConfig(period=4, prune_frac=0.5))
    tparams = init_params(jax.random.PRNGKey(0), tcfg)
    svc = TopologyService(tcfg, TopologyServiceConfig(epoch_every=50))
    sched = StreamScheduler(tparams, tcfg, n_slots=2, chunk_len=6,
                            topology=svc,
                            autopilot=AutopilotConfig(max_depth=3))
    assert sched.autopilot.cfg.max_depth == 1
    sched.close()


# ------------------------------------------------------------------ tiers

def test_tiered_fleet_matches_single_grid_references(params):
    """Streams on a two-tier fleet see bit-identically what they'd see on
    a dedicated single-grid scheduler with their tier's geometry."""
    tiers = [TierConfig("interactive", chunk_len=4, n_slots=2),
             TierConfig("bulk", chunk_len=12, n_slots=2)]

    def submit_split(sched, multi):
        for s in _mixed_sessions(6):
            tier = "interactive" if s.sid % 2 else "bulk"
            if multi or (tier == sched._only):
                sched.submit(s, tier=tier if multi else None)

    multi = StreamScheduler(params, CFG, n_slots=2, tiers=tiers, ingest=True)
    multi._only = None
    submit_split(multi, True)
    got = {s.sid: s for s in multi.run_until_drained()}
    multi.close()
    assert multi.tiers == ("interactive", "bulk")
    assert multi.n_slots == 4
    assert set(multi.n_compiles_by_tier.values()) == {1}
    per_tier = multi.telemetry.per_tier()
    assert set(per_tier) == {"interactive", "bulk"}
    assert per_tier["interactive"]["timesteps"] > 0
    lat = multi.telemetry.tier_percentiles()
    assert set(lat) == {"interactive", "bulk"}

    ref = {}
    for name, C in [("interactive", 4), ("bulk", 12)]:
        solo = StreamScheduler(params, CFG, n_slots=2, chunk_len=C)
        solo._only = name
        submit_split(solo, False)
        ref.update({s.sid: s for s in solo.run_until_drained()})
    _assert_fleet_identical(ref, got)


def test_tier_validation(params):
    with pytest.raises(ValueError, match="duplicate"):
        StreamScheduler(params, CFG, n_slots=2,
                        tiers=[TierConfig("a", 4, 2), TierConfig("a", 8, 2)])
    with pytest.raises(ValueError, match="non-empty"):
        StreamScheduler(params, CFG, n_slots=2, tiers=[])
    with pytest.raises(ValueError):
        TierConfig("x", chunk_len=0, n_slots=2)
    with pytest.raises(ValueError):
        TierConfig("x", chunk_len=4, n_slots=0)
    sched = StreamScheduler(params, CFG, n_slots=2,
                            tiers=[TierConfig("a", 4, 2)])
    with pytest.raises(ValueError, match="unknown tier"):
        sched.submit(StreamSession(sid=0, source=ReplaySource(_events(0, 8))),
                     tier="b")


def test_topology_requires_single_tier(params):
    from repro.core.dsst import DSSTConfig
    from repro.serving import TopologyService, TopologyServiceConfig

    tcfg = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=12,
                     dsst=DSSTConfig(period=4, prune_frac=0.5))
    svc = TopologyService(tcfg, TopologyServiceConfig(epoch_every=50))
    with pytest.raises(ValueError, match="single-tier"):
        StreamScheduler(init_params(jax.random.PRNGKey(0), tcfg), tcfg,
                        n_slots=2, topology=svc,
                        tiers=[TierConfig("a", 4, 2), TierConfig("b", 8, 2)])


# ------------------------------------------------------- 8-device parity

def _run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_qos_8device_matches_serial(params):
    """Tiers + async ingest + adaptive depth on an 8-device sharded grid:
    per-stream results bit-identical to the plain serial single-device
    single-grid references."""
    _run_sub("""
        import jax, numpy as np
        from repro.core.snn import SNNConfig, init_params
        from repro.data.events import make_task
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import (ArrivalConfig, AERStreamSource,
                                   AutopilotConfig, StreamScheduler,
                                   StreamSession, TierConfig)

        assert jax.device_count() == 8
        CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8,
                        t_steps=16)
        params = init_params(jax.random.PRNGKey(0), CFG)
        task = make_task("gesture", n_in=CFG.n_in, t_steps=CFG.t_steps)
        JIT = ArrivalConfig(min_chunk=3, max_chunk=13, mean_gap_s=0.004,
                            start_jitter_s=0.02)

        def sessions():
            return [StreamSession(sid=sid,
                                  source=AERStreamSource(task, n_windows=2,
                                                         seed=sid,
                                                         arrival=JIT),
                                  adapt=(sid % 2 == 0))
                    for sid in range(10)]

        def run(**kw):
            sched = StreamScheduler(params, CFG, **kw)
            for i, s in enumerate(sessions()):
                tier = None
                if "tiers" in kw:
                    tier = "interactive" if s.sid % 2 else "bulk"
                sched.submit(s, tier=tier)
            done = {s.sid: s for s in sched.run_until_drained()}
            sched.close()
            return done, sched

        tiers = [TierConfig("interactive", chunk_len=4, n_slots=8),
                 TierConfig("bulk", chunk_len=12, n_slots=8)]
        got, sched = run(n_slots=8, tiers=tiers, mesh=make_serving_mesh(),
                         ingest=True,
                         autopilot=AutopilotConfig(
                             max_depth=2, decide_every=1, hold_steps=2,
                             warmup_obs=1, deepen_above=0.0,
                             relax_below=0.0))
        assert set(sched.n_compiles_by_tier.values()) == {1}
        assert len(sched.autopilot.depths_visited()) > 1

        ref = {}
        for name, C in [("interactive", 4), ("bulk", 12)]:
            solo = StreamScheduler(params, CFG, n_slots=8, chunk_len=C)
            for s in sessions():
                want = "interactive" if s.sid % 2 else "bulk"
                if want == name:
                    solo.submit(s)
            ref.update({s.sid: s for s in solo.run_until_drained()})

        assert set(ref) == set(got)
        for sid in ref:
            a, b = ref[sid], got[sid]
            assert a.timesteps_fed == b.timesteps_fed
            assert len(a.predictions) == len(b.predictions)
            for pa, pb in zip(a.predictions, b.predictions):
                np.testing.assert_array_equal(pa.logits, pb.logits)
            np.testing.assert_array_equal(a.final_deltas, b.final_deltas)
        print("OK", len(ref))
    """)
