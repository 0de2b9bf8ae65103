"""Packed-bit spikes from push to the chunk program.

Sessions buffer pushed spikes as ``np.packbits`` rows, the scheduler
stages a ``[C, S, ceil(n_in/8)]`` uint8 grid, and the chunk fn widens it
on the device. Spikes are exactly 0 or 1, so the packed path must compute
the same bits as the same spikes fed as float32: pinned here for the
chunk fn (one device, the 8-device slot mesh, ``n_in`` not a multiple of
8) and for a whole scheduler run against a serial float32 replay.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.snn import (SNNConfig, init_params, init_stream_deltas,
                            init_stream_state, serving_params)
from repro.serving import (AdaptConfig, StreamScheduler, StreamSession,
                           make_chunk_fn, unpack_events)

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
# n_in not a multiple of 8 (padding bits in each packed row); the compact
# layout needs n_in == n_hidden, so this one runs dense deltas
CFG_ODD = SNNConfig(n_in=20, n_hidden=32, n_layers=2, n_out=8, t_steps=16)


def _spikes(seed, shape, rate=0.3):
    rng = np.random.default_rng(seed)
    return (rng.random(shape) < rate).astype(np.float32)


# ------------------------------------------------------------- session

@pytest.mark.parametrize("n_in", [32, 20])
def test_push_pop_roundtrip_across_split_pops(n_in):
    """Chunks of any length, in any 0/1 dtype, come back through
    ``unpack_events`` in order, however the pops cut them."""
    sess = StreamSession(sid=0)
    spikes = _spikes(1, (23, n_in))
    cuts = [0, 5, 6, 14, 23]
    kinds = [np.float32, bool, np.int32, np.float64]
    for (a, b), kind in zip(zip(cuts, cuts[1:]), kinds):
        sess.push_events(spikes[a:b].astype(kind))
    assert sess.pending_timesteps() == 23
    got = []
    for want in (4, 7, 9, 9):                # splits chunks, then drains
        rows = sess.pop_chunk(want)
        assert rows.dtype == np.uint8 and rows.shape[1] == -(-n_in // 8)
        got.append(unpack_events(rows, n_in))
    assert [g.shape[0] for g in got] == [4, 7, 9, 3]
    out = np.concatenate(got)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, spikes)
    assert sess.pending_timesteps() == 0


@pytest.mark.parametrize("bad", [0.5, 2.0, -1.0, np.nan, 1e-45])
def test_push_events_rejects_non_binary(bad):
    sess = StreamSession(sid=0)
    chunk = _spikes(2, (4, CFG.n_in))
    chunk[1, 3] = bad
    with pytest.raises(ValueError, match="binary"):
        sess.push_events(chunk)
    assert sess.pending_timesteps() == 0 and sess.n_in == CFG.n_in


def test_events_in_is_exact_spike_count():
    """Each stream's ``events_in`` counter is its exact spike total, read
    off the staged bits."""
    params = init_params(jax.random.PRNGKey(0), CFG)
    sched = StreamScheduler(params, CFG, n_slots=3, chunk_len=5)
    totals = {}
    for sid, t in enumerate((17, 9, 30, 12)):   # 4 streams, 3 lanes
        spikes = _spikes(10 + sid, (t, CFG.n_in), rate=0.2 + 0.1 * sid)
        sess = StreamSession(sid=sid)
        sess.push_events(spikes)
        totals[sid] = float(spikes.sum())
        sched.submit(sess)
    sched.run_until_drained()
    for sid, total in totals.items():
        assert sched.telemetry.stream(sid).events_in == total, sid


# ------------------------------------------------------------- chunk fn

def _chunk_fn_outputs(cfg, compact, packed, steps=3, S=6, C=5):
    params = init_params(jax.random.PRNGKey(0), cfg)
    ex = serving_params(params, cfg) if compact else params
    fn = make_chunk_fn(cfg, AdaptConfig(delta_decay=0.95, delta_clip=0.3))
    st = init_stream_state(cfg, S)
    dl = init_stream_deltas(cfg, S, compact=compact)
    rng = np.random.default_rng(0)
    outs = []
    for _ in range(steps):
        ev = (rng.random((C, S, cfg.n_in)) < 0.3).astype(np.float32)
        valid = rng.random((C, S)) < 0.8
        amask = rng.random(S) < 0.7
        if packed:
            ev = np.packbits(ev.astype(bool), axis=-1)
        dl, st, m = fn(ex, dl, st, ev, valid, amask)
        outs.append(jax.device_get((dl, st, m)))
    assert fn.n_traces() == 1
    return outs


@pytest.mark.parametrize("cfg,compact", [(CFG, True), (CFG_ODD, False)],
                         ids=["compact-n_in32", "dense-n_in20"])
def test_chunk_fn_packed_matches_f32_bits(cfg, compact):
    """Three carried steps, ragged valid, mixed adapt mask, decay and clip
    on: packed uint8 events give the float32 events' deltas, state and
    metrics bit for bit."""
    want = _chunk_fn_outputs(cfg, compact, packed=False)
    got = _chunk_fn_outputs(cfg, compact, packed=True)
    for a, b in zip(want, got):
        la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            np.testing.assert_array_equal(x, y)


def test_sharded_chunk_fn_packed_matches_f32_bits():
    """The 8-device ``("slots",)`` mesh: packed events, each device
    widening only its own slot shard, give the one-device float32 bits
    (compact n_in 32, and dense n_in 20 with padding bits)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import numpy as np, jax
        from repro.core.snn import (SNNConfig, init_params, serving_params,
                                    init_stream_state, init_stream_deltas)
        from repro.launch import sharding as SH
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.adapt import AdaptConfig, make_chunk_fn

        mesh = make_serving_mesh()
        assert SH.slot_devices(mesh) == 8
        S, C = 16, 6
        for n_in, compact in ((32, True), (20, False)):
            cfg = SNNConfig(n_in=n_in, n_hidden=32, n_layers=2, n_out=8,
                            t_steps=16)
            params = init_params(jax.random.PRNGKey(0), cfg)
            ex = serving_params(params, cfg) if compact else params
            adapt = AdaptConfig(delta_decay=0.95, delta_clip=0.3)
            fn1 = make_chunk_fn(cfg, adapt)
            fn8 = make_chunk_fn(cfg, adapt, mesh=mesh)
            st1 = init_stream_state(cfg, S)
            dl1 = init_stream_deltas(cfg, S, compact=compact)
            st8 = jax.device_put(st1, SH.stream_shardings(st1, mesh))
            dl8 = jax.device_put(dl1, SH.slot_sharding(mesh))
            rng = np.random.default_rng(0)
            for i in range(3):
                ev = (rng.random((C, S, n_in)) < 0.3).astype(np.float32)
                valid = rng.random((C, S)) < 0.8
                amask = rng.random(S) < 0.7
                pk = np.packbits(ev.astype(bool), axis=-1)
                dl1, st1, m1 = fn1(ex, dl1, st1, ev, valid, amask)
                dl8, st8, m8 = fn8(ex, dl8, st8, pk, valid, amask)
            assert fn8.n_traces() == 1
            for a, b in zip(jax.tree_util.tree_leaves((dl1, st1, m1)),
                            jax.tree_util.tree_leaves((dl8, st8, m8))):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=560)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout


# ------------------------------------------------------------- scheduler

@pytest.mark.parametrize("depth", [0, 1])
def test_scheduler_matches_serial_f32_replay(depth):
    """A scheduler run (packed staging, ragged stream lengths, lanes freed
    as streams end) delivers the predictions and final deltas of a serial
    replay that feeds the same grid as float32 spikes."""
    S, C = 4, 6
    params = init_params(jax.random.PRNGKey(3), CFG)
    adapt = AdaptConfig(delta_decay=0.97, delta_clip=0.4)
    sched = StreamScheduler(params, CFG, n_slots=S, chunk_len=C,
                            adapt=adapt, pipeline_depth=depth)
    streams = [_spikes(20 + i, (t, CFG.n_in))
               for i, t in enumerate((40, 23, 55, 32))]
    sessions = []
    for sid, ev in enumerate(streams):
        s = StreamSession(sid=sid, adapt=sid != 1)
        s.push_events(ev)
        sched.submit(s)
        sessions.append(s)
    sched.run_until_drained()

    # the replay: lane i holds stream i from the first step on
    fn = make_chunk_fn(CFG, adapt)
    st, dl = init_stream_state(CFG, S), init_stream_deltas(CFG, S)
    amask = np.array([s.adapt for s in sessions])
    preds = {i: [] for i in range(S)}
    final = {}
    for k in range(-(-max(e.shape[0] for e in streams) // C)):
        events = np.zeros((C, S, CFG.n_in), np.float32)
        valid = np.zeros((C, S), bool)
        for i, ev in enumerate(streams):
            part = ev[k * C:(k + 1) * C]
            events[:len(part), i] = part
            valid[:len(part), i] = True
        dl, st, m = fn(sched._exec_params, dl, st, events, valid,
                       amask & valid.any(0))
        m = jax.device_get(m)
        for i, ev in enumerate(streams):
            for t in np.nonzero(m.window_end[:, i])[0]:
                preds[i].append(m.logits[t, i])
            if k * C < ev.shape[0] <= (k + 1) * C:
                final[i] = np.asarray(dl[i])
    for i, s in enumerate(sessions):
        assert len(s.predictions) == len(preds[i]) > 0, i
        for p, want in zip(s.predictions, preds[i]):
            np.testing.assert_array_equal(p.logits, want)
        np.testing.assert_array_equal(s.final_deltas, final[i])
