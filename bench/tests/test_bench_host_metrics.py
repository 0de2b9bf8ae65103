"""The readers of the per-layer metrics that read the scheduler's host
sub-spans and byte counts, on synthetic spans: the expected value where
the program records them, and nothing where it does not (as on a program
that predates them)."""
from __future__ import annotations

import itertools

import pytest

from bench import harness
from repro.obs.trace import Span

_ids = itertools.count(1)


def span(name, dur_s, parent=None, **attrs):
    return Span(name=name, span_id=next(_ids),
                parent_id=parent.span_id if parent is not None else None,
                t0_s=0.0, dur_s=dur_s, thread="main",
                attrs=tuple(sorted(attrs.items())))


def ctx(spans):
    return harness.Context(counts={}, spans=spans, trace=None, peak=None,
                           chips=1)


def step_spans(*, sub_spans: bool):
    """Two grid steps; the second admits two sessions. ``sub_spans=False``
    is what a program without the sub-spans and counts records."""
    out = []
    for admitted in (0, 2):
        counts = ({"h2d_bytes": 1000 + admitted,
                   "leaves_written": 9 * admitted,
                   "bytes_written": 450 * admitted} if sub_spans else {})
        admit = span("sched.admit", 0.010, admitted=admitted,
                     **{k: v for k, v in counts.items() if k != "h2d_bytes"})
        dispatch = span("sched.dispatch", 0.020,
                        **{k: v for k, v in counts.items()
                           if k == "h2d_bytes"})
        retire = span("sched.retire", 0.030)
        out += [admit, dispatch, retire]
        if sub_spans:
            out += [span("admit.write", 0.004, admit) for _ in range(admitted)]
            out += [span("dispatch.transfer", 0.006, dispatch),
                    span("retire.telemetry", 0.012, retire)]
    # a sub-span whose phase began before the window is not counted
    if sub_spans:
        out.append(span("retire.telemetry", 0.5,
                        span("sched.retire", 0.6)))
    return out


@pytest.mark.parametrize("name,expected", [
    ("retire_telemetry_ms.serve", 12.0),
    ("dispatch_transfer_ms.serve", 6.0),
    ("h2d_bytes_per_step.serve", 1001.0),
    ("admit_write_ms_per_session.serve", 4.0),
    ("admit_bytes_per_session.serve", 450.0),
])
def test_reader_on_synthetic_spans(name, expected):
    read = harness.metric_reader(name)
    assert read(ctx(step_spans(sub_spans=True))) == pytest.approx(expected)
    assert read(ctx(step_spans(sub_spans=False))) is None
    assert read(ctx([])) is None


def test_admission_readers_read_nothing_without_admissions():
    quiet = [s for s in step_spans(sub_spans=True)
             if s.name != "admit.write"
             and not (s.name == "sched.admit" and s.attr("admitted"))]
    for name in ("admit_write_ms_per_session.serve",
                 "admit_bytes_per_session.serve"):
        assert harness.metric_reader(name)(ctx(quiet)) is None
