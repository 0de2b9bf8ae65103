"""Retire's counter fold: one array pass over a grid step's lanes.

``StreamScheduler._fold_telemetry`` adds every occupied lane's slice of
the chunk metrics to its stream's row of the telemetry's counter table in
one array add. These tests hold it to the per-lane loop it replaced
(copied below as the reference): per-stream counters bit-identical, tier
totals integer-exact where the counts are integers and within 1e-12
relative for the synaptic-op sums, and a negative increment refused
before anything is added. All on host numpy arrays shaped like
``ChunkMetrics``; no device program runs.
"""
import types

import numpy as np
import pytest

from repro.core.snn import ChunkMetrics
from repro.obs import parse_prometheus_text, prometheus_text
from repro.obs.metrics import Table, TableCell
from repro.serving.scheduler import StreamScheduler
from repro.serving.staging import LaneRecord, StagedChunk
from repro.serving.telemetry import (STREAM_COLUMNS, STREAM_COUNTER_FAMILIES,
                                     STREAM_GAUGE_FAMILIES, FleetTelemetry)

S, C, L = 64, 25, 3
TIER = "default"
TIER_SUMS = ("timesteps", "events_in", "sop_forward", "sop_wu",
             "sop_wu_offered", "windows")


def _metrics(rng):
    """Random ChunkMetrics-shaped host arrays; every lane, empty or not,
    carries values, so a fold that reads the wrong lane shows."""
    f32 = np.float32
    return ChunkMetrics(
        logits=np.zeros((C, S, 2), f32),
        window_end=rng.random((C, S)) < 0.05,
        sop_forward=(rng.random(S) * 3e5).astype(f32),
        sop_wu=(rng.random(S) * 7e4).astype(f32),
        sop_wu_offered=(rng.random(S) * 9e4 + 7e4).astype(f32),
        gate_opened=rng.integers(0, C, (S, L)).astype(f32),
        gate_offered=rng.integers(C, 2 * C, (S, L)).astype(f32),
        local_loss=rng.normal(0.0, 3.0, S).astype(f32),
        steps=rng.integers(0, C + 1, S).astype(f32),
        pre_mag=None, post_mag=None)


def _reference_add_chunk(c, *, steps, events_in, sop_forward, sop_wu,
                         sop_wu_offered, gate_opened, gate_offered, windows,
                         local_loss):
    """``StreamCounters.add_chunk`` as it stood: one Python-float add per
    counter."""
    c["timesteps"] += float(steps)
    c["events_in"] += float(events_in)
    c["sop_forward"] += float(sop_forward)
    c["sop_wu"] += float(sop_wu)
    c["sop_wu_offered"] += float(sop_wu_offered)
    c["gate_opened"] += float(gate_opened)
    c["gate_offered"] += float(gate_offered)
    c["windows"] += int(windows)
    c["local_loss"] += float(local_loss)


def _reference_fold(streams, tier_sums, lanes, m):
    """The per-lane fold as it stood before the counter table, over
    ``lanes`` = [(slot, sid, events_in)]."""
    wend = m.window_end                    # [C, S]
    tsum = {"steps": 0.0, "events_in": 0.0, "sop_forward": 0.0,
            "sop_wu": 0.0, "sop_wu_offered": 0.0, "windows": 0}
    for slot, sid, events_in in lanes:
        steps = float(m.steps[slot])
        sop_forward = float(m.sop_forward[slot])
        sop_wu = float(m.sop_wu[slot])
        sop_wu_offered = float(m.sop_wu_offered[slot])
        windows = int(wend[:, slot].sum())
        counters = streams.setdefault(sid, dict.fromkeys(STREAM_COLUMNS, 0.0))
        _reference_add_chunk(
            counters,
            steps=steps,
            events_in=events_in,
            sop_forward=sop_forward,
            sop_wu=sop_wu,
            sop_wu_offered=sop_wu_offered,
            gate_opened=float(m.gate_opened[slot].sum()),
            gate_offered=float(m.gate_offered[slot].sum()),
            windows=windows,
            local_loss=float(m.local_loss[slot]))
        tsum["steps"] += steps
        tsum["events_in"] += events_in
        tsum["sop_forward"] += sop_forward
        tsum["sop_wu"] += sop_wu
        tsum["sop_wu_offered"] += sop_wu_offered
        tsum["windows"] += windows
    if lanes:                              # record_tier_chunk's adds
        tsum["timesteps"] = tsum.pop("steps")
        for attr in TIER_SUMS:
            tier_sums[attr] += (int(tsum[attr]) if attr == "windows"
                                else float(tsum[attr]))


class _Fleet:
    """The scheduler's side of the fold without a device: a lane→row map
    written at admission, rows gathered at stage, then the real
    ``_fold_telemetry``."""

    def __init__(self):
        self.telemetry = FleetTelemetry()
        self.tier = types.SimpleNamespace(
            name=TIER, lane_row=np.zeros(S, np.intp))
        self.occupant = [None] * S

    def admit(self, slot, sid):
        self.occupant[slot] = types.SimpleNamespace(sid=sid)
        self.tier.lane_row[[slot]] = self.telemetry.rows([sid])

    def stage(self, spikes):
        occupied = [(slot, sess) for slot, sess in enumerate(self.occupant)
                    if sess is not None]
        slots = np.array([slot for slot, _ in occupied], np.intp)
        return StagedChunk(
            events=None, valid=None, adapt_mask=None,
            lanes=[LaneRecord(slot=slot, session=sess, n_fed=C)
                   for slot, sess in occupied],
            retiring=[], merge_slots=(), fed={}, slots=slots, spikes=spikes,
            rows=self.tier.lane_row[slots])

    def retire(self, staged, m):
        StreamScheduler._fold_telemetry(self, self.tier, staged, m)


def _tier_value(tel, attr):
    name = f"serving_tier_{attr}_total"
    return tel.registry.get(name).labels(tier=TIER).value


def test_array_fold_matches_per_lane_loop():
    """64 lanes, a few empty, three grid steps, one lane re-admitted to a
    new stream between steps: every stream's counters bit-identical to
    the per-lane loop's, tier totals exact or within 1e-12 relative."""
    rng = np.random.default_rng(20261022)
    fleet = _Fleet()
    empty = {3, 17, 40, 41, 63}
    for slot in range(S):
        if slot not in empty:
            fleet.admit(slot, 1000 + 7 * slot)
    streams, tier_sums = {}, dict.fromkeys(TIER_SUMS, 0.0)
    for step in range(3):
        if step == 1:
            fleet.occupant[5] = None           # stream 1035 retires ...
            fleet.admit(5, 99_000)             # ... and its lane re-admits
            fleet.admit(40, 99_001)            # an empty lane fills
        if step == 2:
            fleet.occupant[22] = None          # a lane left empty
        spikes = rng.integers(0, 40_000, S)
        m = _metrics(rng)
        staged = fleet.stage(spikes)
        lanes = [(rec.slot, rec.session.sid, float(spikes[rec.slot]))
                 for rec in staged.lanes]
        _reference_fold(streams, tier_sums, lanes, m)
        fleet.retire(staged, m)

    tel = fleet.telemetry
    assert sorted(tel.streams) == sorted(streams)
    assert {1035, 99_000, 99_001} <= set(streams)
    for sid, want in streams.items():
        got = tel.stream(sid)
        for attr in STREAM_COLUMNS:
            assert getattr(got, attr) == want[attr], (sid, attr)
        assert isinstance(got.windows, int)
    for attr in ("timesteps", "events_in", "windows"):
        assert _tier_value(tel, attr) == tier_sums[attr], attr
    for attr in ("sop_forward", "sop_wu", "sop_wu_offered"):
        assert _tier_value(tel, attr) == pytest.approx(
            tier_sums[attr], rel=1e-12, abs=0), attr
    rollup = tel.rollup()
    assert rollup["n_streams"] == len(streams)
    assert rollup["timesteps"] == sum(c["timesteps"] for c in streams.values())


@pytest.mark.parametrize("bad", ["steps", "sop_wu", "gate_offered"])
def test_negative_increment_adds_nothing(bad):
    """A negative counter increment in any lane raises before the fold
    adds anything: every stream's counters and the tier totals read as
    they did before the step."""
    rng = np.random.default_rng(7)
    fleet = _Fleet()
    for slot in range(0, S, 2):
        fleet.admit(slot, slot)
    fleet.retire(fleet.stage(rng.integers(0, 100, S)), _metrics(rng))
    tel = fleet.telemetry
    before = {sid: [getattr(c, a) for a in STREAM_COLUMNS]
              for sid, c in tel.streams.items()}
    tiers_before = [_tier_value(tel, a) for a in TIER_SUMS]
    m = _metrics(rng)
    getattr(m, bad)[10] = -1.0                  # lane 10 is occupied
    with pytest.raises(ValueError, match=">= 0"):
        fleet.retire(fleet.stage(rng.integers(0, 100, S)), m)
    assert {sid: [getattr(c, a) for a in STREAM_COLUMNS]
            for sid, c in tel.streams.items()} == before
    assert [_tier_value(tel, a) for a in TIER_SUMS] == tiers_before


def test_add_chunk_negative_adds_nothing_and_loss_may_fall():
    """The single-row path keeps the same contract; ``local_loss`` is a
    gauge column and may fall."""
    tel = FleetTelemetry()
    c = tel.stream(4)
    kw = dict(steps=5, events_in=9, sop_forward=3.5, sop_wu=1.0,
              sop_wu_offered=2.0, gate_opened=1, gate_offered=3, windows=1,
              local_loss=-0.25)
    c.add_chunk(**kw)
    with pytest.raises(ValueError):
        c.add_chunk(**{**kw, "sop_forward": -1.0})
    assert [getattr(c, a) for a in STREAM_COLUMNS] == [
        5.0, 9.0, 3.5, 1.0, 2.0, 1.0, 3.0, 1, -0.25]


def test_table_grows_and_cells_follow():
    """The counter table doubles past its capacity; every registry child
    still reads its own cell, in the scrape as in the view."""
    tel = FleetTelemetry()
    n = 3 * len(tel._table.data) + 5
    for sid in range(n):
        tel.stream(sid).add_chunk(
            steps=sid, events_in=2 * sid, sop_forward=0.5 * sid, sop_wu=0,
            sop_wu_offered=0, gate_opened=0, gate_offered=0,
            windows=sid % 3, local_loss=-float(sid))
    assert len(tel._table.data) >= n == tel._table.n_rows
    scrape = parse_prometheus_text(prometheus_text(tel.registry))
    for sid in range(n):
        c = tel.stream(sid)
        assert (c.timesteps, c.events_in, c.windows, c.local_loss) == (
            sid, 2 * sid, sid % 3, -float(sid))
        label = f'{{sid="{sid}"}}'
        assert scrape["serving_stream_sop_forward_total" + label] == 0.5 * sid
        assert scrape["serving_stream_local_loss_sum" + label] == -float(sid)
    assert scrape["serving_stream_series"] == n


def test_table_cell_keeps_its_column_rule():
    """A counter cell refuses a negative ``inc``; a gauge cell takes it."""
    table = Table([True, False])
    row = table.add_row()
    counter, gauge = TableCell(table, row, 0), TableCell(table, row, 1)
    counter.inc(2.5)
    gauge.inc(-1.5)
    with pytest.raises(ValueError):
        counter.inc(-1.0)
    assert (counter.value, gauge.value) == (2.5, -1.5)
    assert table.sums().tolist() == [2.5, -1.5]
    assert len(STREAM_COLUMNS) == (len(STREAM_COUNTER_FAMILIES)
                                   + len(STREAM_GAUGE_FAMILIES))
