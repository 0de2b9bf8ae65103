"""The live-DSST serving cell, on the CPU at a small size (one device, an
epoch every three grid steps): a sound run comes out correct against the
reference's whole-fleet epochs and stream replay, across many epochs; the
control and each fault planted under the live path (``bench/faults_live.py``)
make ``correct`` false; the cell is found by name; the reference's epoch
pieces and the new per-layer readers do what they say."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from bench import compare, faults_live, harness
from bench.reference import topology as rtopo
from bench.tests.small import run_small, small_cell
from repro.obs.trace import Span

CELL = "serve_live_dsst_4chip"


def live_cell():
    """The cell at the small size, on one device, an epoch every third
    grid step."""
    cell = small_cell(CELL)
    cell.chips = 1
    cell.config = {**cell.config, "topology_service": {
        **cell.config["topology_service"], "epoch_every": 3}}
    return cell


def test_live_cell_is_found_by_name_on_four_chips():
    c = harness.find_cell(CELL)
    assert c.chips == 4 and c.config["lanes"] == 4096
    assert c.config["topology"] == "live"
    assert c.config["topology_service"]["epoch_every"] == 16
    assert harness.mode_runner(c) is not None
    assert {m["name"] for m in c.end_to_end} == {
        "setup_s", "serve_steps_per_s", "serve_window_latency_p95_ms",
        "serve_peak_bytes_per_stream"}
    names = {m["name"] for m in c.per_layer}
    assert {"topology_epoch_ms.serve", "topology_epoch_device_ms.serve",
            "topology_epoch_hbm_share.serve",
            "factor_fetch_ms.serve"} <= names
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))


def test_sound_live_run_is_correct_across_epochs():
    out = run_small(CELL, cell=live_cell())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    rd = compare.last_readings["program"]
    assert rd["epochs"] >= 2
    assert rd["logit_gap_median"] == 0.0 and rd["base_gap"] == 0.0
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]


def test_live_control_is_not_correct():
    out = run_small(CELL, cell=live_cell(), control="high")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", faults_live.LIVE)
def test_live_fault_is_caught(fault):
    out = run_small(CELL, cell=live_cell(), fault=fault)
    assert not out["correct"], out["checks"]


# ------------------------------------------------------------ the reference

def test_compact_dense_roundtrip_and_projection():
    rng = np.random.default_rng(0)
    L, K, N, keep = 2, 8, 6, 3
    mask = np.zeros((L, K, N), bool)
    for l in range(L):
        for n in range(N):
            mask[l, rng.choice(K, keep, replace=False), n] = True
    dense = np.where(mask, rng.standard_normal((L, K, N)), 0).astype(
        np.float32)
    c = rtopo.compact(dense, mask)
    assert c.shape == (L, N, keep)
    np.testing.assert_array_equal(rtopo.densify(c, mask), dense)
    np.testing.assert_array_equal(
        rtopo.densify(c[..., None, None], mask), dense)
    new = np.roll(mask, 1, axis=1)
    proj = rtopo.project(dense, mask, new)
    both = mask & new
    np.testing.assert_array_equal(proj[both], dense[both])
    assert np.all(proj[~both] == 0.0)


def test_hot_lanes_order_and_eligibility():
    norms = np.array([0.5, 2.0, 2.0, 0.0, 3.0])
    eligible = np.array([True, True, True, True, False])
    assert rtopo.hot_lanes(norms, eligible, 2, 1e-6) == [1, 2]
    assert rtopo.hot_lanes(norms, eligible, 5, 1e-6) == [1, 2, 0]


# ------------------------------------------------------------ the readers

_ids = itertools.count(1)


def span(name, dur_s, parent=None, **attrs):
    return Span(name=name, span_id=next(_ids),
                parent_id=parent.span_id if parent is not None else None,
                t0_s=0.0, dur_s=dur_s, thread="main",
                attrs=tuple(sorted(attrs.items())))


class _Trace:
    def __init__(self, programs):
        self.programs = programs

    def program(self, prefix):
        hits = [v for k, v in self.programs.items() if k.startswith(prefix)]
        return (sum(s for s, _ in hits), sum(n for _, n in hits)) \
            if hits else None


def _ctx(spans, trace=None, peak=None):
    return harness.Context(counts={"epoch_base_bytes": 1e6}, spans=spans,
                           trace=trace, peak=peak, chips=4)


def test_epoch_readers():
    retires = [span("sched.retire", 0.03) for _ in range(4)]
    spans = retires + [span("retire.factors", 0.002, r) for r in retires]
    spans += [span("topology.epoch", 0.010, bytes_projected=8.19e8 - 1e6),
              span("topology.epoch", 0.020, bytes_projected=8.19e8 - 1e6)]
    trace = _Trace({"jit_topology_epoch": (0.008, 4)})
    peak = {"hbm_bytes_per_s": 819e9}
    read = lambda n, c: harness.metric_reader(n)(c)
    c = _ctx(spans, trace, peak)
    assert read("topology_epoch_ms.serve", c) == pytest.approx(15.0)
    assert read("topology_epoch_device_ms.serve", c) == pytest.approx(2.0)
    assert read("topology_epoch_hbm_share.serve", c) == pytest.approx(50.0)
    assert read("factor_fetch_ms.serve", c) == pytest.approx(2.0)
    # a program without the spans, the attribute or the trace: nothing
    bare = _ctx(retires + [span("topology.epoch", 0.01)])
    for name in ("topology_epoch_device_ms.serve",
                 "topology_epoch_hbm_share.serve", "factor_fetch_ms.serve"):
        assert read(name, bare) is None
    assert read("topology_epoch_ms.serve", _ctx(retires)) is None
