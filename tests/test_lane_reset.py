"""Admission's lane reset: one donated, in-place program per stage.

* **Lane isolation.** ``make_reset_lanes``'s program sets every admitted
  lane to ``fresh_lane_state`` bit for bit and leaves every other lane
  bit-identical — compact and dense delta layouts, one device and the
  8-device slot mesh (where the outputs keep the slot shardings).
* **One compile.** The padded ``[S]`` slot vector makes 1, 3 or S
  admitted lanes one trace.
* **Only when needed.** A stage that admits nobody runs no program
  (``programs == 0`` on its ``sched.admit`` span).
* **Donation is safe under pipelining.** At depth 1 a stage re-admits
  lanes whose sessions retire in the step still in flight; their final
  deltas are sliced off the grid before it is donated, so every result
  matches the serial depth-0 scheduler bit for bit.
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.snn import (SNNConfig, init_params, init_stream_deltas,
                            init_stream_state)
from repro.obs import Tracer
from repro.serving import (ReplaySource, StreamScheduler, StreamSession,
                           fresh_lane_state)
from repro.serving.session import make_reset_lanes

CFG = SNNConfig(n_in=32, n_hidden=32, n_layers=2, n_out=8, t_steps=16)
S = 6

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _noisy_grid(compact, seed=0):
    """A ``[S]`` state/delta grid whose every leaf differs from its fresh
    value in every lane."""
    grid = (init_stream_state(CFG, S), init_stream_deltas(CFG, S,
                                                          compact=compact))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + rng.integers(1, 9, a.shape).astype(a.dtype), grid)


def _host_leaves(grid):
    """Host copies of a grid's leaves. Taken from a twin of the grid that
    is donated: on the CPU a host view of an array pins its buffer, and a
    pinned buffer is copied rather than donated."""
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(grid)]


def _slots(admitted):
    out = np.full(S, S, np.int32)
    out[:len(admitted)] = admitted
    return out


def _check_reset(before, after, admitted, compact):
    fresh = jax.tree_util.tree_leaves(fresh_lane_state(CFG, compact=compact))
    for b, a, f in zip(before, jax.tree_util.tree_leaves(after), fresh):
        a = np.asarray(a)
        for lane in range(S):
            want = np.asarray(f)[0] if lane in admitted else b[lane]
            np.testing.assert_array_equal(a[lane], want)


@pytest.mark.parametrize("compact", [True, False], ids=["compact", "dense"])
def test_reset_lanes_matches_single_lane_reference(compact):
    """Admitted lanes equal ``fresh_lane_state`` bit for bit, the others
    keep their bits, the inputs are donated, and 1, 3 or S lanes are one
    trace."""
    reset = make_reset_lanes(CFG, compact)
    for seed, admitted in enumerate(([4], [0, 2, 5], list(range(S)))):
        before = _host_leaves(_noisy_grid(compact, seed))
        state, deltas = _noisy_grid(compact, seed)
        assert deltas.ndim == (6 if compact else 4)
        out = reset(state, deltas, _slots(admitted))
        assert deltas.is_deleted() and state.x_tr.is_deleted()
        _check_reset(before, out, admitted, compact)
    assert reset.n_traces() == 1


def test_reset_lanes_on_the_8device_slot_mesh():
    """Under the slot mesh the program takes and returns the tier's slot
    shardings, and resets exactly the admitted lanes, compact and dense;
    one trace per layout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src") + os.pathsep + _ROOT
    env["JAX_PLATFORMS"] = "cpu"
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.launch import sharding
        from repro.launch.mesh import make_serving_mesh
        from repro.serving.session import make_reset_lanes
        from tests import test_lane_reset as T

        mesh = make_serving_mesh()
        T.S = 16
        for compact in (True, False):
            st_sh = dl_sh = None
            reset = None
            for seed, admitted in enumerate(([9], [0, 7, 15],
                                             list(range(T.S)))):
                before = T._host_leaves(T._noisy_grid(compact, seed))
                state, deltas = T._noisy_grid(compact, seed)
                if reset is None:
                    st_sh = sharding.stream_shardings(state, mesh)
                    dl_sh = sharding.slot_sharding(mesh)
                    reset = make_reset_lanes(T.CFG, compact, st_sh, dl_sh)
                state = jax.device_put(state, st_sh)
                deltas = jax.device_put(deltas, dl_sh)
                out = reset(state, deltas, T._slots(admitted))
                assert deltas.is_deleted()
                for leaf, want in zip(jax.tree_util.tree_leaves(out),
                                      jax.tree_util.tree_leaves(
                                          (st_sh, dl_sh))):
                    assert leaf.sharding.is_equivalent_to(want, leaf.ndim)
                T._check_reset(before, out, admitted, compact)
            assert reset.n_traces() == 1
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    assert "OK" in out.stdout


# ------------------------------------------------ inside the scheduler

@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(0), CFG)


def _events(seed, t, rate=0.3):
    rng = np.random.default_rng(seed)
    return (rng.random((t, CFG.n_in)) < rate).astype(np.float32)


class _Watch:
    """Wraps a tier's reset program: counts calls, and at each call notes
    the in-flight steps whose retiring lanes are being re-admitted (their
    final deltas already sliced off the grid) and checks the grid it was
    handed is deleted after the call."""

    def __init__(self, sched):
        self.tier = sched._tiers[0]
        self.inner = self.tier.reset_lanes
        self.calls, self.readmitted = 0, 0
        self.tier.reset_lanes = self

    def __call__(self, state, deltas, slots):
        self.calls += 1
        admitted = {int(s) for s in slots if s < self.tier.n_slots}
        for fl in self.tier.pipeline:
            if admitted & {slot for slot, _ in fl.staged.retiring}:
                assert fl.deltas is None and fl.snapshots is not None
                self.readmitted += 1
        out = self.inner(state, deltas, slots)
        assert deltas.is_deleted()
        return out


def _drive(params, depth, compact, tracer=None):
    sched = StreamScheduler(params, CFG, n_slots=3, chunk_len=6,
                            pipeline_depth=depth, compact=compact,
                            tracer=tracer)
    watch = _Watch(sched)
    for sid in range(7):
        sched.submit(StreamSession(
            sid=sid,
            source=ReplaySource(_events(sid, (2 + sid % 3) * CFG.t_steps,
                                        rate=0.25 + 0.03 * sid),
                                chunk_len=7),
            adapt=(sid % 2 == 0)))
    done = {s.sid: s for s in sched.run_until_drained()}
    return sched, watch, done


@pytest.mark.parametrize("compact", [True, False], ids=["compact", "dense"])
def test_pipelined_readmission_matches_serial(params, compact):
    """Depth 1 re-admits lanes whose sessions retire in the in-flight
    step, in the same stage; final deltas, predictions, the last grid and
    the counters match the serial scheduler bit for bit (reading a donated
    array would raise)."""
    serial, ws, ds = _drive(params, 0, compact)
    piped, wp, dp = _drive(params, 1, compact)
    assert wp.readmitted > 0 and ws.readmitted == 0
    assert set(ds) == set(dp) == set(range(7))
    for sid in ds:
        a, b = ds[sid], dp[sid]
        assert len(a.predictions) == len(b.predictions) > 0
        for x, y in zip(a.predictions, b.predictions):
            np.testing.assert_array_equal(x.logits, y.logits)
        np.testing.assert_array_equal(a.final_deltas, b.final_deltas)
        assert a.final_deltas.ndim == (5 if compact else 3)
        assert serial.telemetry.stream(sid).timesteps \
            == piped.telemetry.stream(sid).timesteps
    for x, y in zip(jax.tree_util.tree_leaves((serial.state, serial.deltas)),
                    jax.tree_util.tree_leaves((piped.state, piped.deltas))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert ws.inner.n_traces() == wp.inner.n_traces() == 1


def test_stage_without_admission_runs_no_program(params):
    """Only stages that admit someone call the reset program; the others
    record ``programs == 0`` and write nothing."""
    sched, watch, done = _drive(params, 1, None, tracer=Tracer(1 << 14))
    admits = sched.tracer.spans("sched.admit")
    idle = [s for s in admits if not s.attr("admitted")]
    busy = [s for s in admits if s.attr("admitted")]
    assert idle and busy
    assert watch.calls == len(busy) == len(sched.tracer.spans("admit.write"))
    for s in idle:
        assert s.attr("programs") == 0
        assert s.attr("bytes_written") == s.attr("leaves_written") == 0
    assert all(s.attr("programs") == 1 for s in busy)
    assert sum(s.attr("admitted") for s in busy) == len(done) == 7
