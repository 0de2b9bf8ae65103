"""The live-DSST serving cell: a fleet of streams through
``StreamScheduler`` on a ``("slots",)`` mesh of the cell's chips, with a
live ``TopologyService`` rewiring the shared N:M base from the fleet's
activity every ``epoch_every`` grid steps.

Set-up makes the weights from the seed, builds the mesh, the service and
the scheduler, admits the whole fleet (one session per lane, all long
lived), steps until the chunk step has compiled and the staging pipeline
is full, then drains it and forces one epoch, so that the epoch program
compiles in set-up too, and steps once more. The window then calls
``step()`` for ``--seconds``; the epochs that fall due run inside it.

Timing is the serving cells' (``bench/modes/serve.py``):
``serve_steps_per_s``, ``serve_window_latency_p95_ms`` (which holds the
windows that close behind an epoch), and ``serve_peak_bytes_per_stream``,
here the sum over the chips of each chip's peak bytes in use, over the
fleet's lanes.

Correctness (``bench/compare_live.py``): each epoch's inputs are recorded
as the program gave them (accumulated factors, lanes that may merge,
delta norms, the chosen lanes' deltas); after the window the reference
recomputes every epoch from the benchmark's weights and replays a sample
of the streams across them. One more grid step from the fleet's state
checks the cross-chip factor sums against the reference's over every
lane, and one more epoch checks the projection of the sampled lanes.
"""
from __future__ import annotations

import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from bench import compare_live, harness
from bench.epoch_bytes import base_bytes
from bench.flops import per_timestep
from bench.modes.serve import (_Calls, _sample, _session_class,
                               _snn_config, _TimedPredictions, window_closers)
from bench.generator import PlanSource, ServeTraffic


def _recording_service():
    """The program's service, recording what each epoch was given and
    chose (small arrays only; never the delta grid)."""
    from repro.serving import TopologyService

    class RecordingService(TopologyService):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.records: List[Any] = []

        def enqueue(self, *a, **k):
            run = super().enqueue(*a, **k)
            self.records.append(run.record)
            return run

    return RecordingService


def next_chunk(traffic: ServeTraffic, plan, start: int, n: int):
    """Timesteps ``start .. start + n`` of a plan's stream."""
    T = traffic.T
    rows = [traffic.window(plan, t // T)[t % T] for t in range(start,
                                                                start + n)]
    return np.stack(rows)


def peak_bytes_sum(devs) -> int:
    """Peak bytes in use, summed over the chips."""
    return sum(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float, devs,
        fault: str = "", control: str = "") -> Dict[str, Any]:
    import jax
    from repro.launch.mesh import make_serving_mesh
    from repro.obs.trace import Tracer
    from repro.serving import (AdaptConfig, StreamScheduler,
                               TopologyServiceConfig)
    from repro.serving.topology_service import EpochRecord  # noqa: F401
    # (a program whose live epoch records nothing cannot run this cell)

    cfg, mix = cell.config, cell.traffic
    lanes, C, depth = cfg["lanes"], cfg["chunk_len"], cfg["pipeline_depth"]
    T = cfg["t_steps"]
    snn_cfg = _snn_config(cfg)
    setup: Dict[str, float] = {"import": time.perf_counter() - t_start}

    t = time.perf_counter()
    from bench.weights import make_params
    params = jax.block_until_ready(make_params(cfg, seed))
    params0 = jax.device_get(params)
    setup["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    traffic = ServeTraffic(mix, cfg, lanes, seed, C)
    setup["traffic"] = time.perf_counter() - t

    tracer = Tracer(capacity=1 << 17, annotate=True) if trace else None
    mesh = make_serving_mesh(len(devs))
    svc = _recording_service()(snn_cfg,
                               TopologyServiceConfig(**cfg["topology_service"]))
    sched = StreamScheduler(params, snn_cfg, n_slots=lanes, chunk_len=C,
                            adapt=AdaptConfig(**cfg["adapt"]),
                            pipeline_depth=depth, tracer=tracer, mesh=mesh,
                            topology=svc)
    if sched.n_slots != lanes:
        raise ValueError(f"{lanes} lanes do not shard evenly over "
                         f"{len(devs)} chips (got {sched.n_slots})")
    if fault:
        from bench.faults import SERVE, plant_serve
        from bench.faults_live import plant_live
        (plant_serve if fault in SERVE else plant_live)(sched, fault)
    calls = _Calls()
    Session = _session_class()
    sessions: Dict[int, Any] = {}
    for _ in range(lanes):
        plan = traffic.next_plan()
        s = Session(sid=plan.sid, source=PlanSource(traffic, plan),
                    calls=calls)
        s.predictions = _TimedPredictions()
        sessions[plan.sid] = s
        sched.submit(s)
    fed_total: List[int] = []

    def one_step():
        calls.index += 1
        calls.t0.append(time.perf_counter())
        fed = sched.step()
        fed_total.append(sum(fed.values()))

    t = time.perf_counter()
    comp0 = clock.seconds
    one_step()                           # admits the fleet, compiles
    setup["admit_and_first_step"] = time.perf_counter() - t
    while calls.index < 2 + depth:
        one_step()
    t = time.perf_counter()
    sched.flush()                        # then one epoch, to compile it
    sched.maybe_evolve_topology(force=True)
    one_step()
    setup["forced_epoch"] = time.perf_counter() - t
    setup["warm_steps"] = calls.index + 1
    setup["compile_s"] = clock.seconds - comp0
    t = time.perf_counter()
    harness.settle()
    setup["settle"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    # ---- the measured window ----------------------------------------------
    programs0 = clock.programs
    epoch_traces0 = svc.n_program_traces
    epochs0 = len(svc.records)
    gc_clock = harness.GcClock()
    w0 = calls.index + 1
    prof = None
    if trace:
        from bench.trace_reduce import Profile
        prof = Profile(seconds, mix["trace_seconds"])
    t0 = time.perf_counter()
    while True:
        if prof is not None:
            prof.tick(time.perf_counter() - t0)
        one_step()
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    w1 = calls.index
    compiles_in_window = clock.programs - programs0
    gc_clock.stop()
    if prof is not None:
        prof.stop()
    epochs_in_window = len(svc.records) - epochs0
    sched.flush()
    jax.block_until_ready(sched.deltas)
    mem_sum = peak_bytes_sum(devs)
    mem_peak = harness.peak_bytes(devs)

    retired_steps = range(w0 - depth, w1 - depth + 1)
    timesteps = sum(fed_total[s] for s in retired_steps)
    window_s = t1 - t0

    lat_ms, attempted, failed = [], 0, 0
    for s in sessions.values():
        preds = s.predictions
        for i, call in enumerate(window_closers(s.pops, T)):
            if not (w0 <= call <= w1):
                continue
            attempted += 1
            if i >= len(preds) or not np.all(np.isfinite(preds[i].logits)):
                failed += 1
                continue
            lat_ms.append((preds.stamps[i] - calls.t0[call]) * 1e3)
    lat_ms.sort()
    p95 = (statistics.quantiles(lat_ms, n=20, method="inclusive")[-1]
           if len(lat_ms) >= 2 else float("nan"))

    counts = {
        "window_s": window_s, "grid_steps": w1 - w0 + 1,
        "timesteps": timesteps, "windows": attempted,
        "latency_samples": len(lat_ms), "lanes": lanes,
        "epochs": epochs_in_window,
        "epoch_compiles_in_window": svc.n_program_traces - epoch_traces0,
        "chunk_compiles": sched.n_compiles,
        "compiles_in_window": compiles_in_window,
        "gc_s": round(gc_clock.seconds, 6), "gc_full": gc_clock.full,
        "slowest_steps_s": sorted(
            (b - a for a, b in zip(calls.t0[w0:w1 + 1], calls.t0[w0 + 1:])),
            reverse=True)[:3],
        "flops_per_timestep": per_timestep(cfg),
        "epoch_base_bytes": base_bytes(cfg),
        "peak_bytes_per_chip": mem_peak,
    }
    harness.eprint(
        f"set-up: total_s={setup_s:.3f} " + " ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in setup.items())
        + f" cache_hits={clock.cache_hits} programs={clock.programs}")
    harness.eprint("window: " + " ".join(f"{k}={v}" for k, v in
                                        counts.items()))
    harness.eprint(f"epochs: in_window={epochs_in_window} "
                   f"all={len(svc.records)} grid_steps="
                   f"{[r.grid_step for r in svc.records]}")
    if lat_ms:
        harness.eprint(
            f"latency_ms: samples={len(lat_ms)} "
            f"p50={statistics.median(lat_ms):.3f} p95={p95:.3f} "
            f"max={lat_ms[-1]:.3f} (p95 has "
            f"{len(lat_ms) - math.ceil(0.95 * len(lat_ms))} samples above)")

    metrics = {
        "setup_s": (setup_s, "s"),
        "serve_steps_per_s": (timesteps / window_s, "steps/s"),
        "serve_window_latency_p95_ms": (p95, "ms"),
        "serve_peak_bytes_per_stream": (mem_sum / lanes, "B"),
    }

    # ---- correctness -------------------------------------------------------
    t = time.perf_counter()
    slot_of = {s.sid: slot for slot, s in enumerate(sched.grid.occupant)
               if s is not None}
    chosen = _sample(sessions, lanes, mix, seed)
    program = []
    for sid in chosen:
        slot = slot_of[sid]
        s = sessions[sid]
        program.append({
            "sid": sid, "slot": slot, "pops": s.pops,
            "logits": [p.logits for p in s.predictions],
            "delta": np.asarray(sched.deltas[slot]),
            "state": jax.tree_util.tree_map(lambda a: np.asarray(a[slot]),
                                            sched.state)})
    records = [{
        "grid_step": r.grid_step, "k": r.k, "pre": r.pre, "post": r.post,
        "eligible": r.eligible, "norms": np.asarray(r.norms),
        "hot": np.asarray(r.hot), "hot_ok": np.asarray(r.hot_ok),
        "hot_deltas": np.asarray(r.hot_deltas)} for r in svc.records]
    final = {"w": np.asarray(sched.params["hidden"]["w"]),
             "mask": np.asarray(sched.params["hidden"]["mask"])}
    extra = _extra_step(sched, svc, sessions, traffic, C, chosen, slot_of)
    spans = tracer.spans() if tracer is not None else []
    sched.close()
    del sched
    harness.eprint(f"fetch: seconds={time.perf_counter() - t:.3f}")
    t = time.perf_counter()
    checks = compare_live.live_checks(cfg, params0, traffic, program,
                                      records, final, extra, control=control)
    harness.eprint(f"reference: streams={len(program)} "
                   f"epochs={len(records)} "
                   f"seconds={time.perf_counter() - t:.3f}")
    return {
        "metrics": metrics, "attempted": attempted, "failed": failed,
        "checks": checks, "peak_bytes": mem_peak, "counts": counts,
        "spans": spans, "window": (t0, t1), "profile": prof,
    }


def _extra_step(sched, svc, sessions, traffic, C, chosen, slot_of):
    """After the window: one more grid step from the fleet's state (the
    program's cross-chip factor sums, and every lane's inputs for the
    reference's), then one more epoch on that state with no lane merging
    (the sampled lanes before and after its projection, and its mask)."""
    import jax
    S = sched.n_slots
    events = np.zeros((C, S, sched.cfg.n_in), np.float32)
    valid = np.zeros((C, S), bool)
    amask = np.zeros(S, bool)
    for slot, s in enumerate(sched.grid.occupant):
        if s is None:
            continue
        start = sum(n for _, n in s.pops)
        events[:, slot] = next_chunk(traffic, traffic.plans[s.sid], start, C)
        valid[:, slot] = True
        amask[slot] = s.adapt
    ev, va, am = jax.device_put((events, valid, amask), sched._input_sh)
    _, _, m = sched.chunk_fn(sched._exec_params, sched.deltas, sched.state,
                             ev, va, am)
    pre_sum, post_sum = jax.device_get((m.pre_mag, m.post_mag))
    st = jax.device_get(sched.state)
    state = {"v": st.layers.v, "tr": st.layers.tr, "tr_pc": st.layers.tr_pc,
             "tr_cc": st.layers.tr_cc, "x_tr": st.x_tr,
             "ss_mean": st.ss_mean, "t_win": st.t_in_window}
    deltas = np.asarray(sched.deltas)
    slots = [slot_of[sid] for sid in chosen]
    pre = np.asarray(svc.pre)
    fn = svc.program(sched.mesh)
    new_params, _, new_deltas, _, _ = fn(
        sched.params, sched.deltas, pre, np.asarray(svc.post),
        np.zeros(S, bool), svc.level_k())
    after = np.stack([np.asarray(new_deltas[s]) for s in slots])
    return {"events": events.transpose(1, 0, 2), "valid": valid.T,
            "pre_sum": pre_sum, "post_sum": post_sum, "state": state,
            "deltas": deltas,
            "epoch": {"pre": pre, "before": deltas[slots], "after": after,
                      "mask": np.asarray(new_params["hidden"]["mask"])}}
