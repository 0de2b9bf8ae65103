"""Serving: concurrent event streams through one jitted slot-grid step.

Throughput (events/s, timesteps/s) and p50/p99 grid-step latency vs the
number of concurrent streams, with the per-stream energy rollup priced at
the chip's 0.6 V operating point. Hard guarantee checked here: after the
first compilation, multiplexing any number of streams through the fixed
slot grid triggers **zero recompilation** (jit cache size stays 1) — the
serving analogue of the continuous batcher's static-shape discipline.

``--devices N`` sweeps the sharded slot grid: the same workload is driven
with the slot axis sharded over the first 1, 2, ..., N of this process's
devices (``jax.devices()[:n]`` — one process, so a chip is never held by
a parent while a child waits for it) and events/s scaling vs the 1-device
baseline is reported. Each row names its platform and ``device_kind``. On
a CPU host (``XLA_FLAGS=--xla_force_host_platform_device_count=N``) the
"devices" share physical cores, so this validates the sharded path's
overhead and mechanics rather than demonstrating real speedup.

``--evolve EVERY`` drives the same workload with and without the live
topology service (DSST prune/regrow epochs every EVERY grid steps, hot
streams folded into the base) and reports events/s for both plus epoch
count and mask-change fraction — the cost of evolving connectivity under
traffic. The hard guarantee extends: topology swaps included, the grid
step still compiles exactly once. A quick with/without pair also runs as
part of the default ``run()`` so the harness tracks it.

Rows carry the observability signals next to throughput: the per-phase
stage/dispatch/retire p50/p99 walls (``phase_ms=...``) and — for the
pipelined A/B rows — the measured host/device **overlap ratio**
(``overlap=``, ~1 host-bound / ~0 device-bound; docs/OBSERVABILITY.md).
Under ``benchmarks.run --json`` each row additionally ships a structured
``metrics`` dict and a full ``obs`` registry snapshot.

``--pipeline on|off`` / ``--factors on|off`` A/B the serving hot path
against the serial baseline (pipeline off, DSST factors compiled in):
double-buffered event staging overlaps host chunk packing with device
compute, and ``want_factors=off`` compiles the O(S·(K+N))-per-timestep
DSST factor accumulators out of the chunk scan. Rows report events/s for
the baseline and the configured mode plus their ratio; trajectories are
bit-identical across all four combinations (pinned in
``tests/test_serving_pipeline.py``). A quick A/B pair also rides in the
default ``run()`` rows.

``--density quick|full`` A/Bs the compact ``[S, L, J, T, bk, bo]`` delta
layout (the hot-path default — only kept N:M blocks are stored and the
chunk jaxpr carries no dense mask) against the dense ``[S, L, Kmax, N]``
baseline at each N:M density: events/s for both plus the **measured**
weight-state footprint from the ``serving_bytes_held`` gauge. Compact
delta bytes scale ~linearly with density (the paper's "3.8× reduced
on-chip memory" analogue); dense bytes stay flat. A single quick pair
also rides in the default ``run()`` rows.
"""
from __future__ import annotations

import jax
import numpy as np

from repro.core.snn import SNNConfig, init_params
from repro.data.events import make_task
from repro.serving import (AERStreamSource, ArrivalConfig, AutopilotConfig,
                           FleetTelemetry, IngestConfig, StreamScheduler,
                           StreamSession, TaskStreamSource, TierConfig,
                           TopologyService, TopologyServiceConfig)

N_IN, N_HIDDEN, T_STEPS = 64, 64, 20
CHUNK_LEN = 10

# printed by ``benchmarks.run --dryrun`` so the module's focused CLI modes
# are discoverable (and their registration can't rot silently)
CLI_FLAGS = ("--devices N | --evolve EVERY | --pipeline on|off "
             "| --factors on|off | --density quick|full "
             "| --tiers on|off --adaptive on|off [--json PATH]")

# the QoS A/B's traffic: AER-packed chunks (real decode cost at poll) on
# jittered Poisson arrivals — the shape async ingestion is for
QOS_ARRIVAL = ArrivalConfig(min_chunk=3, max_chunk=CHUNK_LEN + 3,
                            mean_gap_s=1e-3, start_jitter_s=0.01)


def _drive(n_streams: int, n_slots: int, n_windows: int, seed: int = 0,
           mesh=None, evolve_every: int = 0, merge_top: int = 2,
           pipeline: int = 0, want_factors=None, tracer=None,
           sparsity=None, compact=None, ingest=None, autopilot=None,
           tiers=None, tier_of=None, aer: bool = False, arrival=None):
    cfg = SNNConfig(n_in=N_IN, n_hidden=N_HIDDEN, n_layers=2, n_out=10,
                    t_steps=T_STEPS,
                    **({} if sparsity is None else {"sparsity": sparsity}))
    params = init_params(jax.random.PRNGKey(seed), cfg)
    task = make_task("gesture", n_in=N_IN, t_steps=T_STEPS, seed=seed)
    topo = None
    if evolve_every:
        topo = TopologyService(cfg, TopologyServiceConfig(
            epoch_every=evolve_every, merge_top=merge_top))
    sched = StreamScheduler(params, cfg, n_slots=n_slots, chunk_len=CHUNK_LEN,
                            mesh=mesh, topology=topo, pipeline_depth=pipeline,
                            want_factors=want_factors, tracer=tracer,
                            compact=compact, ingest=ingest,
                            autopilot=autopilot, tiers=tiers)
    arrival = arrival or ArrivalConfig(min_chunk=4, max_chunk=CHUNK_LEN,
                                       mean_gap_s=1e-4)
    Source = AERStreamSource if aer else TaskStreamSource
    for sid in range(n_streams):
        sched.submit(StreamSession(
            sid=sid,
            source=Source(task, n_windows=n_windows, seed=sid,
                          arrival=arrival)),
            tier=tier_of(sid) if tier_of is not None else None)
    sched.step()                     # warmup step compiles the grid(s)
    sched.flush()                    # ...and lands its bookkeeping (pipeline)
    compiles_after_warmup = sched.n_compiles
    # measured window excludes warmup on both sides of the rate: fresh
    # telemetry drops the warmup step's latency AND its counted events
    # (topology epochs keep counting in the service itself)
    sched.telemetry = FleetTelemetry()
    done = sched.run_until_drained()
    sched.close()                    # stop the ingest worker, if any
    assert len(done) == n_streams, (len(done), n_streams)
    assert compiles_after_warmup == 1 and sched.n_compiles == 1, \
        f"slot-grid step recompiled: {sched.n_compiles} variants"
    return sched


def _phase_str(tel) -> str:
    """Compact per-phase p50/p99 for the derived column, e.g.
    ``phase_ms=stage:0.4/1.1,dispatch:0.2/0.5,retire:0.3/0.9``."""
    ph = tel.phase_percentiles()
    parts = [f"{p}:{d['p50_ms']:.2f}/{d['p99_ms']:.2f}"
             for p, d in sorted(ph.items())]
    return "phase_ms=" + ",".join(parts) if parts else "phase_ms=none"


def _row_extras(sched) -> dict:
    """Structured extras for the ``--json`` artifact: the obs-derived
    numbers (overlap ratio, per-phase p50/p99) plus a full registry
    snapshot of the run's metrics."""
    tel = sched.telemetry
    r = tel.rollup()
    metrics = {
        "events_per_s": r["events_per_s"],
        "timesteps_per_s": r["timesteps_per_s"],
        "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
        "overlap_ratio": r["overlap_ratio"],
        "grid_steps": r["grid_steps"],
        "compiles": sched.n_compiles,
    }
    for phase, d in tel.phase_percentiles().items():
        metrics[f"phase_{phase}_p50_ms"] = d["p50_ms"]
        metrics[f"phase_{phase}_p99_ms"] = d["p99_ms"]
        metrics[f"phase_{phase}_total_s"] = d["total_s"]
    return {"metrics": metrics, "obs": tel.registry.snapshot()}


def run(quick: bool = True):
    rows = []
    frozen_baseline = None
    cases = [(8, 8, 2), (32, 32, 2)] if quick else \
        [(8, 8, 4), (32, 32, 4), (64, 32, 4)]
    for n_streams, n_slots, n_windows in cases:
        sched = _drive(n_streams, n_slots, n_windows)
        if (n_streams, n_slots, n_windows) == _evolve_case(quick):
            frozen_baseline = sched      # reused by the evolve row below
        r = sched.telemetry.rollup()
        per = sched.telemetry.per_stream()
        mean_uw = float(np.mean([p["power_uW"] for p in per]))
        rows.append({
            "name": f"serving/streams{n_streams}_slots{n_slots}",
            "us_per_call": r["p50_ms"] * 1e3,
            "derived": (f"events/s={r['events_per_s']:.0f}"
                        f" ts/s={r['timesteps_per_s']:.0f}"
                        f" p99_ms={r['p99_ms']:.2f}"
                        f" util={sched.utilization:.2f}"
                        f" skip={r['wu_skip_rate']:.2f}"
                        f" stream_uW={mean_uw:.1f}"
                        f" {_phase_str(sched.telemetry)}"
                        f" compiles={sched.n_compiles}"),
            **_row_extras(sched),
        })
    rows += run_evolve(quick=quick, frozen=frozen_baseline)
    rows += run_ab(quick=quick)
    rows += run_density(quick=True, densities=[0.2])
    rows += run_qos(quick=True)
    return rows


# ---------------------------------------------------------------------------
# --density: compact vs dense delta layout across N:M densities
# ---------------------------------------------------------------------------

def run_density(quick: bool = True, densities=None):
    """Same workload in the compact ``[S, L, J, T, bk, bo]`` layout vs the
    dense ``[S, L, Kmax, N]`` baseline at each N:M density. Reports
    events/s for both (``rel`` >= 1.0 means compact does not regress) and
    the *measured* weight-state footprint from the ``serving_bytes_held``
    gauge — compact delta bytes must scale ~linearly with density while
    the dense baseline stays flat."""
    if densities is None:
        densities = [0.125, 0.25, 0.5] if quick else [0.125, 0.2, 0.25,
                                                      0.5, 0.75]
    n_streams, n_slots, n_windows = (8, 8, 2) if quick else (32, 32, 2)
    rows = []
    for density in densities:
        sparsity = 1.0 - density
        dense = _drive(n_streams, n_slots, n_windows, sparsity=sparsity,
                       compact=False)
        comp = _drive(n_streams, n_slots, n_windows, sparsity=sparsity,
                      compact=True)
        rd, rc = dense.telemetry.rollup(), comp.telemetry.rollup()
        bd, bc = dense.telemetry.bytes_held(), comp.telemetry.bytes_held()
        spec = comp.cfg.spec(N_IN)
        actual = spec.n / spec.m          # the realized N:M density
        rel = rc["events_per_s"] / rd["events_per_s"] \
            if rd["events_per_s"] else 0.0
        rows.append({
            "name": f"serving/density{actual:.3f}_streams{n_streams}",
            "us_per_call": rc["p50_ms"] * 1e3,
            "derived": (f"events/s={rc['events_per_s']:.0f}"
                        f" dense_events/s={rd['events_per_s']:.0f}"
                        f" rel={rel:.2f}"
                        f" delta_bytes={bc['deltas']:.0f}"
                        f" dense_delta_bytes={bd['deltas']:.0f}"
                        f" param_bytes={bc['params']:.0f}"
                        f" dense_param_bytes={bd['params']:.0f}"
                        f" compiles={comp.n_compiles}"),
            **_row_extras(comp),
        })
    return rows


# ---------------------------------------------------------------------------
# --pipeline / --factors: hot-path A/B vs the serial baseline
# ---------------------------------------------------------------------------

def run_ab(quick: bool = True, pipeline: bool = True, factors: bool = False):
    """Baseline (serial staging, DSST factors compiled in) vs the configured
    hot path on the same workload. ``rel`` >= 1.0 means the configured mode
    is at least as fast; the pipelined/factor-free path must not regress
    (per-stream trajectories are bit-identical either way — only *when*
    host work happens changes, never what the device computes)."""
    n_streams, n_slots, n_windows = (8, 8, 2) if quick else (32, 32, 4)
    base = _drive(n_streams, n_slots, n_windows, pipeline=0,
                  want_factors=True)
    # (pipeline=off, factors=on) IS the baseline — don't drive the same
    # config twice just to print a noise-around-1.0 ratio
    conf = base if (not pipeline and factors) else _drive(
        n_streams, n_slots, n_windows,
        pipeline=1 if pipeline else 0, want_factors=factors)
    rb = base.telemetry.rollup()
    rc = conf.telemetry.rollup()
    rel = rc["events_per_s"] / rb["events_per_s"] \
        if rb["events_per_s"] else 0.0
    tag = (f"pipe{'on' if pipeline else 'off'}_"
           f"fac{'on' if factors else 'off'}")
    return [{
        "name": f"serving/hotpath_{tag}_streams{n_streams}",
        "us_per_call": rc["p50_ms"] * 1e3,
        "derived": (f"events/s={rc['events_per_s']:.0f}"
                    f" baseline_events/s={rb['events_per_s']:.0f}"
                    f" rel={rel:.2f}"
                    f" overlap={rc['overlap_ratio']:.2f}"
                    f" p99_ms={rc['p99_ms']:.2f}"
                    f" baseline_p99_ms={rb['p99_ms']:.2f}"
                    f" {_phase_str(conf.telemetry)}"
                    f" compiles={conf.n_compiles}"),
        **_row_extras(conf),
    }]


# ---------------------------------------------------------------------------
# --tiers / --adaptive: QoS tiers + async ingestion + adaptive depth A/B
# ---------------------------------------------------------------------------

def run_qos(quick: bool = True, tiers: bool = True, adaptive: bool = True):
    """Three-way A/B on jittered AER traffic (decode cost at every poll):

    * ``qos_base`` — the single-grid serial reference: inline polling,
      pipeline depth 0;
    * ``qos_async`` — async ingest worker plus (``adaptive=on``) the
      depth autopilot, same single grid; ``rel`` >= 1.0 means moving
      decode off the critical path and deepening under a host-bound
      signal bought fleet throughput;
    * ``qos_tiers`` — the same fleet split over an ``interactive``
      (short-chunk) and a ``bulk`` (long-chunk) grid, ingest + autopilot
      on; reports per-tier p50/p99, the chosen-depth timeline, and
      interactive p99 against the single-grid baseline's p99.

    Trajectories are bit-identical across all three (pinned in
    tests/test_serving_qos.py) — this measures wall-clock shape only.
    """
    n_streams, n_slots, n_windows = (8, 8, 2) if quick else (32, 16, 4)
    kw = dict(aer=True, arrival=QOS_ARRIVAL)
    ap_cfg = AutopilotConfig(decide_every=2, hold_steps=4, warmup_obs=1) \
        if adaptive else None

    base = _drive(n_streams, n_slots, n_windows, **kw)
    rb = base.telemetry.rollup()
    rows = [{
        "name": f"serving/qos_base_streams{n_streams}",
        "us_per_call": rb["p50_ms"] * 1e3,
        "derived": (f"events/s={rb['events_per_s']:.0f}"
                    f" p99_ms={rb['p99_ms']:.2f}"
                    f" {_phase_str(base.telemetry)}"
                    f" compiles={base.n_compiles}"),
        **_row_extras(base),
    }]

    asyn = _drive(n_streams, n_slots, n_windows, ingest=IngestConfig(),
                  autopilot=ap_cfg, pipeline=0 if adaptive else 1, **kw)
    ra = asyn.telemetry.rollup()
    rel = ra["events_per_s"] / rb["events_per_s"] \
        if rb["events_per_s"] else 0.0
    timeline = (list(map(list, asyn.autopilot.timeline))
                if asyn.autopilot is not None else [])
    row = {
        "name": (f"serving/qos_async_"
                 f"{'adaptive' if adaptive else 'fixed'}"
                 f"_streams{n_streams}"),
        "us_per_call": ra["p50_ms"] * 1e3,
        "derived": (f"events/s={ra['events_per_s']:.0f}"
                    f" baseline_events/s={rb['events_per_s']:.0f}"
                    f" rel={rel:.2f}"
                    f" depth={ra['pipeline_depth']:.0f}"
                    f" depth_changes={ra['depth_changes']}"
                    f" ingest_chunks={ra['ingest_chunks']}"
                    f" overlap={ra['overlap_ratio']:.2f}"
                    f" compiles={asyn.n_compiles}"),
        **_row_extras(asyn),
    }
    row["metrics"].update(baseline_events_per_s=rb["events_per_s"],
                          baseline_p99_ms=rb["p99_ms"], rel=rel,
                          depth_timeline=timeline,
                          depth_changes=ra["depth_changes"],
                          ingest_chunks=ra["ingest_chunks"],
                          ingest_queue_peak=ra["ingest_queue_peak"])
    rows.append(row)

    if not tiers:
        return rows
    half = max(2, n_slots // 2)
    tier_cfgs = [TierConfig("interactive", chunk_len=4, n_slots=half),
                 TierConfig("bulk", chunk_len=CHUNK_LEN + 6, n_slots=half)]
    tiered = _drive(n_streams, n_slots, n_windows, tiers=tier_cfgs,
                    tier_of=lambda sid: "interactive" if sid % 2 else "bulk",
                    ingest=IngestConfig(), autopilot=ap_cfg,
                    pipeline=0 if adaptive else 1, **kw)
    rt = tiered.telemetry.rollup()
    lat = tiered.telemetry.tier_percentiles()
    rel_t = rt["events_per_s"] / rb["events_per_s"] \
        if rb["events_per_s"] else 0.0
    int_p99 = lat.get("interactive", {}).get("p99_ms", 0.0)
    bulk_p99 = lat.get("bulk", {}).get("p99_ms", 0.0)
    timeline = (list(map(list, tiered.autopilot.timeline))
                if tiered.autopilot is not None else [])
    row = {
        "name": f"serving/qos_tiers_streams{n_streams}",
        "us_per_call": rt["p50_ms"] * 1e3,
        "derived": (f"events/s={rt['events_per_s']:.0f}"
                    f" baseline_events/s={rb['events_per_s']:.0f}"
                    f" rel={rel_t:.2f}"
                    f" interactive_p99_ms={int_p99:.2f}"
                    f" bulk_p99_ms={bulk_p99:.2f}"
                    f" baseline_p99_ms={rb['p99_ms']:.2f}"
                    f" depth_changes={rt['depth_changes']}"
                    f" ingest_chunks={rt['ingest_chunks']}"
                    f" compiles={tiered.n_compiles}"),
        **_row_extras(tiered),
    }
    row["metrics"].update(baseline_events_per_s=rb["events_per_s"],
                          baseline_p99_ms=rb["p99_ms"], rel=rel_t,
                          tier_interactive_p50_ms=lat.get(
                              "interactive", {}).get("p50_ms", 0.0),
                          tier_interactive_p99_ms=int_p99,
                          tier_bulk_p50_ms=lat.get("bulk", {}).get(
                              "p50_ms", 0.0),
                          tier_bulk_p99_ms=bulk_p99,
                          depth_timeline=timeline,
                          depth_changes=rt["depth_changes"],
                          ingest_chunks=rt["ingest_chunks"],
                          ingest_queue_peak=rt["ingest_queue_peak"])
    rows.append(row)
    return rows


# ---------------------------------------------------------------------------
# --evolve EVERY: live topology epochs vs a frozen-topology baseline
# ---------------------------------------------------------------------------

def _evolve_case(quick: bool):
    return (8, 8, 2) if quick else (32, 32, 4)


def run_evolve(quick: bool = True, every: int = 0, frozen=None):
    """Same workload, frozen topology vs live DSST epochs every ``every``
    grid steps; reports the throughput cost and the connectivity churn.
    ``every=0`` picks a cadence the workload actually reaches (the quick
    case drains in ~8 grid steps, the full case in ~13 — an ``every``
    beyond that measures a frozen fleet twice). ``frozen`` reuses an
    already-driven baseline scheduler for the same case instead of
    re-driving it."""
    if not every:
        every = 4 if quick else 6
    n_streams, n_slots, n_windows = _evolve_case(quick)
    frozen = frozen or _drive(n_streams, n_slots, n_windows)
    live = _drive(n_streams, n_slots, n_windows, evolve_every=every)
    rf = frozen.telemetry.rollup()
    rl = live.telemetry.rollup()
    svc = live.topology
    assert svc.epoch_idx > 0, \
        f"every={every} exceeds the workload's grid steps: zero epochs ran"
    mask_change = float(np.mean([e.mask_change for e in svc.events]))
    slowdown = rl["events_per_s"] / rf["events_per_s"] \
        if rf["events_per_s"] else 0.0
    return [{
        "name": f"serving/evolve{every}_streams{n_streams}",
        "us_per_call": rl["p50_ms"] * 1e3,
        "derived": (f"events/s={rl['events_per_s']:.0f}"
                    f" frozen_events/s={rf['events_per_s']:.0f}"
                    f" rel={slowdown:.2f}"
                    f" epochs={svc.epoch_idx}"
                    f" mask_change={mask_change:.4f}"
                    f" pruned={sum(e.pruned for e in svc.events)}"
                    f" merged={sum(len(e.merged_slots) for e in svc.events)}"
                    f" compiles={live.n_compiles}"),
        **_row_extras(live),
    }]


# ---------------------------------------------------------------------------
# --devices N: slot-throughput scaling of the sharded grid
# ---------------------------------------------------------------------------

SWEEP_STREAMS, SWEEP_SLOTS, SWEEP_WINDOWS = 64, 64, 2


def run_devices_sweep(max_devices: int):
    """Drive the sharded slot grid over the first 1, 2, 4, ...,
    ``max_devices`` devices of this process and report events/s scaling
    against the 1-device row."""
    from repro.launch.mesh import make_serving_mesh
    devs = jax.devices()
    if len(devs) < max_devices:
        raise RuntimeError(
            f"--devices {max_devices} needs {max_devices} devices, found "
            f"{len(devs)}; on a CPU host set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={max_devices}")
    counts, d = [], 1
    while d < max_devices:
        counts.append(d)
        d *= 2
    counts.append(max_devices)
    dev = f"{devs[0].platform}:{devs[0].device_kind}"
    rows, base = [], None
    for n in counts:
        mesh = make_serving_mesh(n) if n > 1 else None
        sched = _drive(SWEEP_STREAMS, SWEEP_SLOTS, SWEEP_WINDOWS, mesh=mesh)
        r = sched.telemetry.rollup()
        if base is None:
            base = r["events_per_s"]
        rows.append({
            "name": f"serving/devices{n}_slots{sched.n_slots}",
            "us_per_call": r["p50_ms"] * 1e3,
            "derived": (f"device={dev}"
                        f" events/s={r['events_per_s']:.0f}"
                        f" scale_x={r['events_per_s'] / base:.2f}"
                        f" ts/s={r['timesteps_per_s']:.0f}"
                        f" p99_ms={r['p99_ms']:.2f}"
                        f" compiles={sched.n_compiles}"),
        })
    return rows


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="sweep the sharded slot grid over this process's "
                         "first 1..N devices")
    ap.add_argument("--evolve", type=int, default=0, metavar="EVERY",
                    help="live topology epochs every EVERY grid steps, "
                         "vs a frozen-topology baseline")
    ap.add_argument("--pipeline", choices=["on", "off"], default=None,
                    help="A/B the double-buffered staging pipeline against "
                         "the serial baseline")
    ap.add_argument("--factors", choices=["on", "off"], default=None,
                    help="A/B compiling the DSST factor accumulators out of "
                         "the chunk scan (off) vs in (on)")
    ap.add_argument("--density", choices=["quick", "full"], default=None,
                    help="A/B the compact delta layout against the dense "
                         "baseline across N:M densities (events/s + "
                         "measured bytes held)")
    ap.add_argument("--tiers", choices=["on", "off"], default=None,
                    help="A/B QoS tiers (interactive + bulk chunk grids) "
                         "against the single-grid baseline on jittered "
                         "AER traffic")
    ap.add_argument("--adaptive", choices=["on", "off"], default=None,
                    help="enable the occupancy-driven pipeline-depth "
                         "autopilot in the QoS A/B (off: fixed depth 1)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write the rows as a repro-bench/1 artifact")
    args = ap.parse_args()
    rows = None
    if args.tiers is not None or args.adaptive is not None:
        rows = run_qos(quick=True, tiers=(args.tiers != "off"),
                       adaptive=(args.adaptive != "off"))
    elif args.density:
        rows = run_density(quick=(args.density == "quick"))
    elif args.devices:
        rows = run_devices_sweep(args.devices)
    elif args.evolve:
        rows = run_evolve(quick=False, every=args.evolve)
    elif args.pipeline is not None or args.factors is not None:
        # unspecified halves stay at the baseline setting, so each flag can
        # be A/B'd in isolation or combined (--pipeline on --factors off)
        rows = run_ab(quick=False,
                      pipeline=(args.pipeline == "on"),
                      factors=(args.factors != "off"))
    else:
        for row in run(quick=True):
            print(row)
    if rows is not None:
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        if args.json:
            from benchmarks.run import write_artifact
            write_artifact(args.json, rows)
