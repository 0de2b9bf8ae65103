"""``bench/trace_reduce.py`` on a small trace recorded on a TPU v5e.

The trace (``data/small.xplane.pb``) holds three runs of a jitted
``chunk_fn`` inside ``sched.stage`` / ``sched.dispatch`` /
``sched.retire`` > ``sched.device_wait`` annotations, all inside the
``bench.traced`` window annotation, with a 10 ms host sleep in each stage.
"""
from __future__ import annotations

import os

import pytest

from bench import trace_reduce

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace_reduce.reduce_file(DATA)


def test_window_and_busy_time(red):
    assert red.n_devices == 1
    assert 0.03 < red.window_s < 5.0        # three stages sleep 10 ms each
    assert 0.0 < red.busy_s < red.window_s
    assert 0.0 < red.idle_share < 1.0


def test_program_time_under_a_stable_name(red):
    secs, runs = red.program("jit_chunk_fn")
    assert runs == 3
    # the only program: its executions cover the device's busy time
    assert secs == pytest.approx(red.busy_s, rel=0.01)
    assert red.program("jit_no_such_program") is None


def test_idle_gaps_are_named_by_host_spans(red):
    names = dict(red.idle_gaps)
    assert names, red.idle_gaps
    assert "sched.stage" in names           # the host sleeps in each stage
    assert names["sched.stage"] >= 0.025
    assert sum(names.values()) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)


def test_top_device_ops(red):
    assert 0 < len(red.top_ops) <= 10
    secs = [s for _, s in red.top_ops]
    assert secs == sorted(secs, reverse=True)


def test_union_of_overlapping_intervals():
    assert trace_reduce._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_trace_without_window_is_refused(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace_reduce.find_xplane(str(tmp_path))
