"""Per-stream and fleet-level serving telemetry, on the obs registry.

The chip's power story is counted events priced at measured constants
(core/energy.py); the serving runtime keeps that bookkeeping per stream so
a fleet operator can answer "which streams are hot, which are coasting on
the gate, what does a slot-second cost". Counters are monotone by
construction — every update adds a non-negative per-chunk quantity (the
``obs.metrics.Table`` underneath *raises* on a negative increment) —
and per-stream separable: a slot's counters only ever receive that slot's
lane of the chunk metrics.

Since the observability PR, ``FleetTelemetry`` is a facade over a
:class:`repro.obs.metrics.MetricsRegistry`: stream counters are labeled
``serving_stream_*_total{sid=...}`` counter families, step/phase wall
times land in **bounded fixed-bucket histograms** (the old unbounded
``step_latencies_s`` list is gone — memory is O(1) in steps, p50/p99 are
interpolated within ~10% bucket width), and the whole registry exports as
Prometheus text / JSONL / a benchmark artifact via ``repro.obs.export``.

The per-stream values live in one float64 table (``obs.metrics.Table``),
a row per sid ever served and a column per :data:`STREAM_COLUMNS` entry;
the ``sid`` children of those families are views of its cells, so the
scheduler's retire folds every lane of a grid step with one array add
(:meth:`FleetTelemetry.fold_streams`) and a scrape reads the same cells.

Beyond whole-step wall time the telemetry now attributes **per-phase**
wall (stage / dispatch / retire / flush — fed by the scheduler's spans,
each tagged with the grid step it belongs to even when pipelining blurs
their wall-clock order) and the per-step **host/device overlap ratio**:
``hidden / (hidden + wait)`` where *hidden* is the time an in-flight step
spent computing while the host staged the next one, and *wait* is the
retire-phase device block. ~1 means the fleet is host-bound (a deeper
pipeline buys nothing); ~0 means device-bound (staging hides nothing).
This is the occupancy signal adaptive ``pipeline_depth`` control needs.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Deque, Dict, List, Optional, Sequence

import numpy as np

from repro.core.energy import OperatingPoint, report
from repro.obs.metrics import (LATENCY_BUCKETS_S, QUEUE_DEPTH_BUCKETS,
                               RATIO_BUCKETS, MetricsRegistry, Table,
                               TableCell)

# every per-stream counter family: attribute name -> (metric name, help)
STREAM_COUNTER_FAMILIES = {
    "timesteps": ("serving_stream_timesteps_total",
                  "valid timesteps advanced"),
    "events_in": ("serving_stream_events_in_total",
                  "input spikes consumed"),
    "sop_forward": ("serving_stream_sop_forward_total",
                    "forward synaptic ops"),
    "sop_wu": ("serving_stream_sop_wu_total",
               "weight-update MACs actually paid"),
    "sop_wu_offered": ("serving_stream_sop_wu_offered_total",
                       "weight-update MACs offered to the gate"),
    "gate_opened": ("serving_stream_gate_opened_total",
                    "gate-open decisions"),
    "gate_offered": ("serving_stream_gate_offered_total",
                     "gate decisions offered"),
    "windows": ("serving_stream_windows_total",
                "completed T-step windows (predictions)"),
}

# cumulative but NOT monotone (a local loss can be negative) — gauge-backed
STREAM_GAUGE_FAMILIES = {
    "local_loss": ("serving_stream_local_loss_sum",
                   "summed local OSSL loss"),
}

# the counter table's columns, in order: the counters, then local_loss
STREAM_COLUMNS = (*STREAM_COUNTER_FAMILIES, *STREAM_GAUGE_FAMILIES)
_COLUMN = {attr: col for col, attr in enumerate(STREAM_COLUMNS)}

PHASES = ("stage", "dispatch", "retire", "flush")

# per-tier QoS counter families: attribute name -> (metric name, help).
# These are *additive* next to the per-stream families above — the stream
# families keep their single ``sid`` label (exporter goldens and the
# phase-percentile keying depend on it); tier rollups get their own
# ``tier``-labeled families instead of a second label on the old ones.
TIER_COUNTER_FAMILIES = {
    "timesteps": ("serving_tier_timesteps_total",
                  "valid timesteps advanced, by QoS tier"),
    "events_in": ("serving_tier_events_in_total",
                  "input spikes consumed, by QoS tier"),
    "sop_forward": ("serving_tier_sop_forward_total",
                    "forward synaptic ops, by QoS tier"),
    "sop_wu": ("serving_tier_sop_wu_total",
               "weight-update MACs actually paid, by QoS tier"),
    "sop_wu_offered": ("serving_tier_sop_wu_offered_total",
                       "weight-update MACs offered to the gate, by QoS tier"),
    "windows": ("serving_tier_windows_total",
                "completed T-step windows (predictions), by QoS tier"),
}


class StreamCounters:
    """Monotone per-stream event counters (energy-model inputs).

    A view of one ``sid``'s row of the fleet's counter table: reads
    (``c.timesteps`` etc.) return the live cells, which are also the
    registry's ``{sid}`` children of the stream families. The counter
    columns are monotone by construction — a negative increment raises
    before anything is added.
    """

    def __init__(self, sid: int, table: Table, registry: MetricsRegistry):
        self.sid = sid
        self.row = row = table.add_row()
        self._table = table
        for attr, (name, help) in STREAM_COUNTER_FAMILIES.items():
            registry.counter(name, help, labels=("sid",)).bind(
                TableCell(table, row, _COLUMN[attr]), sid=str(sid))
        for attr, (name, help) in STREAM_GAUGE_FAMILIES.items():
            registry.gauge(name, help, labels=("sid",)).bind(
                TableCell(table, row, _COLUMN[attr]), sid=str(sid))

    def __getattr__(self, attr):
        col = _COLUMN.get(attr)
        if col is None or "_table" not in self.__dict__:
            raise AttributeError(attr)
        value = float(self._table.data[self.row, col])
        return int(value) if attr == "windows" else value

    def add_chunk(self, *, steps, events_in, sop_forward, sop_wu,
                  sop_wu_offered, gate_opened, gate_offered, windows,
                  local_loss) -> None:
        """Fold one grid step's slice of the chunk metrics into this
        stream's row (the single-row form of
        :meth:`FleetTelemetry.fold_streams`)."""
        inc = [steps, events_in, sop_forward, sop_wu, sop_wu_offered,
               gate_opened, gate_offered, int(windows), local_loss]
        self._table.add([self.row], np.array([inc], np.float64))

    @property
    def wu_skip_rate(self) -> float:
        """Fraction of offered WU MACs the activity gate skipped (0.0 when
        nothing was offered — e.g. an adapt=False stream)."""
        if self.sop_wu_offered <= 0:
            return 0.0
        return 1.0 - self.sop_wu / self.sop_wu_offered

    def energy(self, op: Optional[OperatingPoint] = None) -> dict:
        """This stream's counters priced at operating point ``op`` (the
        chip's 0.6 V low-power point by default): the ``core.energy``
        report dict + ``sid``/``timesteps``/``windows``."""
        rep = report(self.sop_forward, self.sop_wu, self.sop_wu_offered,
                     self.timesteps, op=op)
        out = rep.as_dict()
        out["sid"] = self.sid
        out["timesteps"] = self.timesteps
        out["windows"] = self.windows
        return out


class FleetTelemetry:
    """Rollup across streams + host-side step/phase latency + overlap.

    Pass (or read) ``registry`` to share one :class:`MetricsRegistry`
    across subsystems and export everything in one Prometheus scrape.
    """

    def __init__(self, op: Optional[OperatingPoint] = None,
                 registry: Optional[MetricsRegistry] = None,
                 max_epoch_events: int = 256):
        self.op = op or OperatingPoint.low_power()
        self.registry = registry or MetricsRegistry()
        self.streams: Dict[int, StreamCounters] = {}
        self._table = Table([attr in STREAM_COUNTER_FAMILIES
                             for attr in STREAM_COLUMNS])
        self._lock = threading.Lock()
        self._steps = self.registry.counter(
            "serving_grid_steps_total", "scheduler grid steps dispatched")
        self._step_hist = self.registry.histogram(
            "serving_step_latency_seconds",
            "host wall time of one StreamScheduler.step() call",
            buckets=LATENCY_BUCKETS_S)
        self._phase_hist = self.registry.histogram(
            "serving_phase_seconds",
            "per-phase host wall time, attributed to the owning grid step",
            labels=("phase",), buckets=LATENCY_BUCKETS_S)
        self._flush_wall = self.registry.counter(
            "serving_flush_seconds_total",
            "pipeline-flush wall (retires after the last grid step)")
        self._overlap_hist = self.registry.histogram(
            "serving_overlap_ratio",
            "per-step host/device overlap: hidden / (hidden + wait)",
            buckets=RATIO_BUCKETS)
        self._hidden_s = self.registry.counter(
            "serving_overlap_hidden_seconds_total",
            "device compute hidden behind host staging")
        self._wait_s = self.registry.counter(
            "serving_device_wait_seconds_total",
            "retire-phase blocks on device results")
        self._topo_epochs = self.registry.counter(
            "serving_topology_epochs_total", "live DSST prune/regrow epochs")
        self._topo_pruned = self.registry.counter(
            "serving_topology_pruned_total", "connections pruned by epochs")
        self._topo_regrown = self.registry.counter(
            "serving_topology_regrown_total", "connections regrown by epochs")
        self._topo_merged = self.registry.counter(
            "serving_streams_merged_total", "hot streams folded into base")
        self._topo_mask_change = self.registry.gauge(
            "serving_topology_mask_change", "last epoch's mask-change frac")
        self._topo_mask_change_sum = self.registry.counter(
            "serving_topology_mask_change_sum",
            "summed per-epoch mask-change fractions (mean = sum / epochs)")
        self._bytes_held = self.registry.gauge(
            "serving_bytes_held",
            "resident bytes of serving weight state (params = the exec "
            "weight rep the chunk fn consumes, deltas = the per-stream "
            "adaptation tensor) — the memory-accounting A/B signal for the "
            "compact vs dense layout", labels=("kind",))
        # -- QoS tiers / adaptive depth / async ingest ------------------------
        self._tier_step_hist = self.registry.histogram(
            "serving_tier_step_seconds",
            "host wall of one tier's slice of a grid step",
            labels=("tier",), buckets=LATENCY_BUCKETS_S)
        self._tier_phase_hist = self.registry.histogram(
            "serving_tier_phase_seconds",
            "per-tier per-phase host wall time",
            labels=("tier", "phase"), buckets=LATENCY_BUCKETS_S)
        self._tier_counters = {
            attr: self.registry.counter(name, help, labels=("tier",))
            for attr, (name, help) in TIER_COUNTER_FAMILIES.items()}
        self._depth_gauge = self.registry.gauge(
            "serving_pipeline_depth",
            "current staging pipeline depth (autopilot-set or fixed)")
        self._depth_changes = self.registry.counter(
            "serving_pipeline_depth_changes_total",
            "adaptive depth changes applied at drain-safe boundaries")
        self._overlap_ema = self.registry.gauge(
            "serving_overlap_ema",
            "the depth autopilot's EMA of the per-step overlap ratio")
        self._ingest_chunks = self.registry.counter(
            "serving_ingest_chunks_total",
            "source chunks drained from the async ingest queues")
        self._ingest_queue_peak = self.registry.gauge(
            "serving_ingest_queue_peak_chunks",
            "high-water per-stream ingest queue depth (backpressure caps "
            "it at the configured capacity)")
        self._ingest_drain_hist = self.registry.histogram(
            "serving_ingest_drained_chunks",
            "chunks released to session buffers per poll-window drain",
            buckets=QUEUE_DEPTH_BUCKETS)
        # -- admission and host<->device traffic -----------------------------
        self._admissions = self.registry.counter(
            "serving_admissions_total", "sessions admitted into a lane")
        self._admit_bytes = self.registry.counter(
            "serving_admit_bytes_total",
            "bytes output by admission's lane writes: every rewritten grid "
            "leaf whole, plus the fresh lane")
        self._h2d_bytes = self.registry.counter(
            "serving_h2d_bytes_total",
            "bytes of the staged events/valid/adapt-mask buffers put on the "
            "device at dispatch")
        self._stream_series = self.registry.gauge(
            "serving_stream_series",
            "live per-stream counter records (one per sid served; a retired "
            "sid's series is never dropped)")
        # recent-events ring: the per-epoch *log* is bounded (a long-lived
        # fleet otherwise grows it forever — the lint's OBS01 class), while
        # the exact aggregates live in the registry counters above and
        # topology_rollup() reads those, so truncation loses no totals.
        self.topology_epochs: Deque[dict] = deque(maxlen=max_epoch_events)

    @property
    def steps(self) -> int:
        """Grid steps recorded (dispatches; flush retires excluded)."""
        return int(self._steps.value)

    def stream(self, sid: int) -> StreamCounters:
        """The (created-on-first-use) per-stream counter record for ``sid``.
        Creation is locked so concurrent sources racing on a new sid get
        the same record (never two counter children for one stream)."""
        with self._lock:
            if sid not in self.streams:
                self.streams[sid] = StreamCounters(
                    sid, self._table, self.registry)
                self._stream_series.set(float(len(self.streams)))
            return self.streams[sid]

    def rows(self, sids: Sequence[int]) -> np.ndarray:
        """The counter-table rows of ``sids`` (records created on first
        use), as an index array for :meth:`fold_streams`."""
        return np.array([self.stream(sid).row for sid in sids], np.intp)

    def fold_streams(self, tier: str, rows: np.ndarray, **lanes) -> None:
        """Fold one retired grid step into the distinct counter rows
        ``rows [n]`` with one array add, and the lanes' sums into
        ``tier``'s counters. ``lanes`` gives every :data:`STREAM_COLUMNS`
        entry as an ``[n]`` array. A negative counter increment raises
        before anything is added."""
        inc = np.stack([lanes[attr] for attr in STREAM_COLUMNS], axis=1,
                       dtype=np.float64)
        with self._lock:
            self._table.add(rows, inc)
        total = dict(zip(STREAM_COLUMNS, inc.sum(0).tolist()))
        self.record_tier_chunk(
            tier, timesteps=total["timesteps"],
            events_in=total["events_in"], sop_forward=total["sop_forward"],
            sop_wu=total["sop_wu"], sop_wu_offered=total["sop_wu_offered"],
            windows=total["windows"])

    def record_step(self, latency_s: float) -> None:
        """Log one grid step's host wall time (one ``step()`` call — under
        a staging pipeline the retire inside belongs to an earlier grid
        step, but the *sum* over steps still accounts every phase exactly
        once; per-phase attribution lives in ``record_phase``)."""
        self._steps.inc()
        self._step_hist.observe(float(latency_s))

    def record_flush(self, latency_s: float) -> None:
        """Log pipeline-flush wall time (retiring in-flight steps after the
        last grid step). Not a grid step — excluded from the latency
        percentiles, but included in the throughput wall so pipelined
        events/s never get a free final step."""
        self._flush_wall.inc(float(latency_s))

    def record_phase(self, phase: str, latency_s: float) -> None:
        """Log one phase's host wall time (stage/dispatch/retire/flush).
        The scheduler calls this from the span that also carries the
        owning ``grid_step`` — so phase sums reconcile with step walls
        regardless of pipeline reordering (pinned in tests)."""
        self._phase_hist.labels(phase=phase).observe(float(latency_s))

    def record_overlap(self, hidden_s: float, wait_s: float) -> float:
        """Log one retired step's host/device overlap; returns the ratio.

        ``hidden_s``: how long the step was in flight while the host did
        useful work (dispatch → retire-start). ``wait_s``: how long retire
        then blocked on the device. Serial (unpipelined) steps record
        hidden=0 → ratio 0.
        """
        hidden_s, wait_s = max(0.0, float(hidden_s)), max(0.0, float(wait_s))
        denom = hidden_s + wait_s
        ratio = hidden_s / denom if denom > 0 else 0.0
        self._hidden_s.inc(hidden_s)
        self._wait_s.inc(wait_s)
        self._overlap_hist.observe(ratio)
        return ratio

    def record_tier_step(self, tier: str, latency_s: float) -> None:
        """Log one tier's slice of a grid step's host wall (the per-tier
        stage→dispatch[→retire] block inside ``step()``) — the histogram
        behind the per-tier p50/p99 the QoS bench rows report."""
        self._tier_step_hist.labels(tier=tier).observe(float(latency_s))

    def record_tier_phase(self, tier: str, phase: str,
                          latency_s: float) -> None:
        """Per-tier per-phase host wall (the ``tier``-labeled companion of
        ``record_phase`` — that family keeps its single ``phase`` label)."""
        self._tier_phase_hist.labels(tier=tier, phase=phase).observe(
            float(latency_s))

    def record_tier_chunk(self, tier: str, *, timesteps, events_in,
                          sop_forward, sop_wu, sop_wu_offered,
                          windows) -> None:
        """Fold one retired grid step's tier-summed metrics into the
        ``tier``-labeled counter families (the per-stream counters record
        the same quantities per sid; these are the QoS rollup view)."""
        c = self._tier_counters
        c["timesteps"].labels(tier=tier).inc(float(timesteps))
        c["events_in"].labels(tier=tier).inc(float(events_in))
        c["sop_forward"].labels(tier=tier).inc(float(sop_forward))
        c["sop_wu"].labels(tier=tier).inc(float(sop_wu))
        c["sop_wu_offered"].labels(tier=tier).inc(float(sop_wu_offered))
        c["windows"].labels(tier=tier).inc(int(windows))

    def record_depth(self, depth: int, changed: bool = False) -> None:
        """Log the pipeline depth now in force; ``changed=True`` counts an
        autopilot change applied at a drain-safe boundary."""
        self._depth_gauge.set(float(depth))
        if changed:
            self._depth_changes.inc()

    def record_overlap_ema(self, ema: float) -> None:
        """Export the autopilot's overlap-ratio EMA (the control signal —
        next to the raw per-step ``serving_overlap_ratio`` histogram)."""
        self._overlap_ema.set(float(ema))

    def record_ingest(self, chunks: int, queue_peak: int) -> None:
        """Log one poll-window drain of the async ingest queues: chunks
        released to session buffers this tick, plus the worker's lifetime
        high-water per-stream queue depth (bounded by the configured
        capacity — the backpressure invariant the QoS tests assert)."""
        self._ingest_chunks.inc(int(chunks))
        self._ingest_queue_peak.set(float(queue_peak))
        self._ingest_drain_hist.observe(float(chunks))

    def record_admissions(self, sessions: int, bytes_written: int) -> None:
        """Log one admission pass: sessions admitted and the bytes its lane
        writes output (shape arithmetic at the call site — no device
        sync)."""
        self._admissions.inc(int(sessions))
        self._admit_bytes.inc(int(bytes_written))

    def record_h2d(self, nbytes: int) -> None:
        """Log the bytes one dispatch put on the device."""
        self._h2d_bytes.inc(int(nbytes))

    def record_bytes_held(self, params_bytes: int, delta_bytes: int) -> None:
        """Log the resident serving weight-state bytes (scheduler-measured
        ``.nbytes`` of the exec weight rep and the delta tensor). Gauges,
        not counters: re-recorded every grid step, they track the *current*
        layout — a topology swap or layout change moves them."""
        self._bytes_held.labels(kind="params").set(float(params_bytes))
        self._bytes_held.labels(kind="deltas").set(float(delta_bytes))
        self._bytes_held.labels(kind="total").set(
            float(params_bytes + delta_bytes))

    def bytes_held(self) -> dict:
        """Last-recorded resident bytes {params, deltas, total} (0 before
        the first grid step)."""
        fam = self.registry.get("serving_bytes_held")
        out = {"params": 0.0, "deltas": 0.0, "total": 0.0}
        if fam is not None:
            for values, child in fam.samples():
                kind = dict(zip(fam.labelnames, values)).get("kind", "total")
                out[kind] = float(child.value)
        return out

    def record_topology_epoch(self, *, grid_step: int, pruned: int,
                              regrown: int, mask_change: float,
                              merged_streams: int) -> None:
        """Log one live DSST prune/regrow epoch (topology_service.py)."""
        self._topo_epochs.inc()
        self._topo_pruned.inc(int(pruned))
        self._topo_regrown.inc(int(regrown))
        self._topo_merged.inc(int(merged_streams))
        self._topo_mask_change.set(float(mask_change))
        self._topo_mask_change_sum.inc(float(mask_change))
        with self._lock:
            self.topology_epochs.append({
                "grid_step": int(grid_step), "pruned": int(pruned),
                "regrown": int(regrown), "mask_change": float(mask_change),
                "merged_streams": int(merged_streams)})

    # -- rollup --------------------------------------------------------------
    def latency_percentiles(self) -> dict:
        """p50/p99 of recorded grid-step wall times, in milliseconds
        (interpolated from the bounded histogram — within one ~10% bucket
        of the exact list-based values the old telemetry computed)."""
        if self._step_hist.count == 0:
            return {"p50_ms": 0.0, "p99_ms": 0.0}
        return {"p50_ms": self._step_hist.percentile(50) * 1e3,
                "p99_ms": self._step_hist.percentile(99) * 1e3}

    def phase_percentiles(self) -> dict:
        """Per-phase ``{phase: {"p50_ms", "p99_ms", "total_s"}}`` for every
        phase that recorded at least one observation."""
        out = {}
        for values, child in self._phase_hist.samples():
            if child.count:
                out[values[0]] = {"p50_ms": child.percentile(50) * 1e3,
                                  "p99_ms": child.percentile(99) * 1e3,
                                  "total_s": child.sum}
        return out

    def tier_percentiles(self) -> dict:
        """Per-tier ``{tier: {"p50_ms", "p99_ms", "total_s"}}`` of the
        tier-step wall histogram — the per-tier latency view the QoS
        bench rows record (interactive p99 vs bulk p99)."""
        out = {}
        for values, child in self._tier_step_hist.samples():
            if child.count:
                out[values[0]] = {"p50_ms": child.percentile(50) * 1e3,
                                  "p99_ms": child.percentile(99) * 1e3,
                                  "total_s": child.sum}
        return out

    def per_tier(self) -> dict:
        """Per-tier counter rollup + energy: ``{tier: {timesteps,
        events_in, windows, wu_skip_rate, energy}}`` for every tier that
        retired at least one chunk (empty on a pre-tier fleet)."""
        acc: Dict[str, dict] = {}
        for attr, (name, _help) in TIER_COUNTER_FAMILIES.items():
            fam = self.registry.get(name)
            if fam is None:
                continue
            for values, child in fam.samples():
                acc.setdefault(values[0], {})[attr] = float(child.value)
        out = {}
        for tier, c in sorted(acc.items()):
            offered = c.get("sop_wu_offered", 0.0)
            out[tier] = {
                "timesteps": c.get("timesteps", 0.0),
                "events_in": c.get("events_in", 0.0),
                "windows": int(c.get("windows", 0)),
                "wu_skip_rate": (1.0 - c.get("sop_wu", 0.0) / offered
                                 if offered > 0 else 0.0),
                "energy": report(c.get("sop_forward", 0.0),
                                 c.get("sop_wu", 0.0), offered,
                                 c.get("timesteps", 0.0),
                                 op=self.op).as_dict(),
            }
        return out

    def tier_rollup(self) -> dict:
        """The QoS additions to :meth:`rollup`: per-tier counters/energy,
        per-tier latency percentiles, the depth/ingest state."""
        return {
            "tiers": self.per_tier(),
            "tier_latency": self.tier_percentiles(),
            "pipeline_depth": float(self._depth_gauge.value),
            "depth_changes": int(self._depth_changes.value),
            "ingest_chunks": int(self._ingest_chunks.value),
            "ingest_queue_peak": int(self._ingest_queue_peak.value),
        }

    def overlap_ratio(self) -> float:
        """Aggregate host/device overlap over the whole run:
        ``hidden_total / (hidden_total + wait_total)`` (0.0 serial)."""
        denom = self._hidden_s.value + self._wait_s.value
        return self._hidden_s.value / denom if denom > 0 else 0.0

    def rollup(self) -> dict:
        """Fleet-level summary: summed stream counters, throughput rates
        (events/s, timesteps/s over the recorded step + flush wall),
        latency percentiles, overlap ratio, fleet energy, and the topology
        rollup. See docs/SERVING.md / docs/OBSERVABILITY.md for the field
        glossary."""
        total = dict(zip(STREAM_COLUMNS, self._table.sums().tolist()))
        timesteps = total["timesteps"]
        events_in = total["events_in"]
        sop_forward = total["sop_forward"]
        sop_wu = total["sop_wu"]
        sop_wu_offered = total["sop_wu_offered"]
        wall = self._step_hist.sum + self._flush_wall.value
        out = {
            "n_streams": len(self.streams),
            "grid_steps": self.steps,
            "timesteps": timesteps,
            "events_in": events_in,
            "windows": int(total["windows"]),
            "wu_skip_rate": (1.0 - sop_wu / sop_wu_offered
                             if sop_wu_offered > 0 else 0.0),
            "fleet_energy": report(sop_forward, sop_wu, sop_wu_offered,
                                   timesteps, op=self.op).as_dict(),
            "events_per_s": events_in / wall if wall > 0 else 0.0,
            "timesteps_per_s": timesteps / wall if wall > 0 else 0.0,
            "overlap_ratio": self.overlap_ratio(),
            "bytes_held": self.bytes_held(),
            **self.latency_percentiles(),
            **self.topology_rollup(),
            **self.tier_rollup(),
        }
        return out

    def topology_rollup(self) -> dict:
        """Aggregate topology-epoch stats (counts, mask-change mean, streams
        merged); all zeros for a frozen fleet. Read from the registry
        counters, not the event log — ``topology_epochs`` is a bounded
        recent-events ring, so these totals stay exact past its horizon."""
        epochs = int(self._topo_epochs.value)
        return {
            "topology_epochs": epochs,
            "topology_pruned": int(self._topo_pruned.value),
            "topology_regrown": int(self._topo_regrown.value),
            "topology_mask_change_mean":
                (float(self._topo_mask_change_sum.value) / epochs
                 if epochs else 0.0),
            "streams_merged": int(self._topo_merged.value),
        }

    def per_stream(self) -> List[dict]:
        """Each stream's energy report (sid-sorted) at the fleet's
        operating point."""
        return [c.energy(self.op) for _, c in sorted(self.streams.items())]
