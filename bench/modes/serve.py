"""Serving cells: a fleet of streams through ``StreamScheduler``.

Set-up makes the weights from the seed, builds the scheduler, submits the
initial fleet (one session per lane) and steps until the chunk step has
compiled, the staging pipeline is full and, under churn, a session has
retired and its replacement been admitted. The window then calls
``step()`` for ``--seconds``; a retired session is replaced at once by the
next session of the traffic plan, so every lane stays busy.

Timing is taken on the host clock around the scheduler's own calls:

* ``serve_steps_per_s`` — valid stream-timesteps of the grid steps retired
  inside the window, over the window's seconds;
* ``serve_window_latency_p95_ms`` — per T-step window closed by a grid step
  staged inside the window: from the start of that ``step()`` call (its
  stage phase packs the window's last timestep) to the moment the
  prediction is appended to the session;
* ``serve_peak_bytes_per_stream`` — the chip's peak bytes in use after the
  window, over the lanes.

Correctness replays a sample of the sessions, drawn from the seed, through
the plain reference (``bench/reference/snn.py``) once the window has
closed and the fleet is freed.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
import time
from typing import Any, Dict, List

import numpy as np

from bench import harness
from bench.compare import serve_checks
from bench.flops import per_timestep
from bench.generator import PlanSource, ServeTraffic


def _snn_config(cfg):
    from repro.core.dsst import DSSTConfig
    from repro.core.gating import GatingConfig
    from repro.core.snn import SNNConfig
    keys = {f.name for f in dataclasses.fields(SNNConfig)}
    kw = {k: v for k, v in cfg.items() if k in keys}
    kw["dsst"] = DSSTConfig(**cfg["dsst"])
    kw["gating"] = GatingConfig(**cfg["gating"])
    return SNNConfig(**kw)


class _Calls:
    """Which ``step()`` call is running, and when each began."""

    def __init__(self):
        self.index = -1
        self.t0: List[float] = []


class _TimedPredictions(list):
    """A session's prediction list that stamps each delivery."""

    def __init__(self):
        super().__init__()
        self.stamps: List[float] = []      # perf_counter at each append

    def append(self, item):
        self.stamps.append(time.perf_counter())
        super().append(item)


def _session_class():
    from repro.serving import StreamSession

    @dataclasses.dataclass
    class BenchSession(StreamSession):
        """A session that logs each chunk the scheduler pops from it, by
        the ``step()`` call that popped it."""
        pops: List[tuple] = dataclasses.field(default_factory=list)
        calls: Any = None

        def pop_chunk(self, max_len: int):
            chunk = super().pop_chunk(max_len)
            self.pops.append((self.calls.index, int(chunk.shape[0])))
            return chunk

    return BenchSession


def window_closers(pops, T: int):
    """For a session's pop log, the ``step()`` call that packed the last
    timestep of each of its windows, in window order."""
    out, fed = [], 0
    for call, n in pops:
        before, fed = fed, fed + n
        out.extend([call] * (fed // T - before // T))
    return out


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        clock: harness.CompileClock, t_start: float, devs,
        fault: str = "", control: str = "") -> Dict[str, Any]:
    import jax
    from repro.obs.trace import Tracer
    from repro.serving import AdaptConfig, StreamScheduler

    cfg, mix = cell.config, cell.traffic
    lanes, C, depth = cfg["lanes"], cfg["chunk_len"], cfg["pipeline_depth"]
    T = cfg["t_steps"]
    snn_cfg = _snn_config(cfg)
    setup: Dict[str, float] = {"import": time.perf_counter() - t_start}

    t = time.perf_counter()
    from bench.weights import make_params
    params = jax.block_until_ready(make_params(cfg, seed))
    setup["weights"] = time.perf_counter() - t

    t = time.perf_counter()
    traffic = ServeTraffic(mix, cfg, lanes, seed, C)
    setup["traffic"] = time.perf_counter() - t

    tracer = Tracer(capacity=1 << 17, annotate=True) if trace else None
    sched = StreamScheduler(params, snn_cfg, n_slots=lanes, chunk_len=C,
                            adapt=AdaptConfig(**cfg["adapt"]),
                            pipeline_depth=depth, tracer=tracer)
    if fault:
        from bench.faults import plant_serve
        plant_serve(sched, fault)
    calls = _Calls()
    Session = _session_class()
    sessions: Dict[int, Any] = {}

    def submit():
        plan = traffic.next_plan()
        s = Session(sid=plan.sid, source=PlanSource(traffic, plan),
                    calls=calls)
        s.predictions = _TimedPredictions()
        sessions[plan.sid] = s
        sched.submit(s)

    for _ in range(lanes):
        submit()
    n_retired = 0
    fed_total: List[int] = []

    def one_step():
        nonlocal n_retired
        calls.index += 1
        calls.t0.append(time.perf_counter())
        fed = sched.step()
        fed_total.append(sum(fed.values()))
        while n_retired < len(sched.retired):   # keep every lane busy
            n_retired += 1
            submit()

    t = time.perf_counter()
    comp0 = clock.seconds
    one_step()                           # admits the fleet, compiles
    setup["admit_and_first_step"] = time.perf_counter() - t
    churn = mix.get("session_windows") is not None
    while calls.index < 2 + depth or (churn and n_retired == 0):
        one_step()
    setup["warm_steps"] = calls.index + 1
    setup["compile_s"] = clock.seconds - comp0
    t = time.perf_counter()
    harness.settle()
    setup["settle"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    # ---- the measured window ----------------------------------------------
    programs0 = clock.programs
    gc_clock = harness.GcClock()
    w0 = calls.index + 1                 # first call inside the window
    admitted0 = len(sessions)
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
    w1 = calls.index                     # last call inside the window
    compiles_in_window = clock.programs - programs0
    gc_clock.stop()
    if prof is not None:
        prof.stop()
    submitted_in_window = len(sessions) - admitted0
    sched.flush()
    jax.block_until_ready(sched.deltas)
    mem_peak = harness.peak_bytes(devs)

    # grid steps retired inside the window: those staged by calls
    # w0 - depth .. w1 - depth (their retire runs depth calls later)
    retired_steps = range(w0 - depth, w1 - depth + 1)
    timesteps = sum(fed_total[s] for s in retired_steps)
    window_s = t1 - t0

    # windows closed by steps staged in the window, and their latency
    lat_ms, attempted, failed = [], 0, 0
    for s in sessions.values():
        closers = window_closers(s.pops, T)
        preds = s.predictions
        for i, call in enumerate(closers):
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
        "sessions_submitted": submitted_in_window,
        "compiles_in_window": compiles_in_window,
        "gc_s": round(gc_clock.seconds, 6), "gc_full": gc_clock.full,
        "slowest_steps_s": sorted(
            (b - a for a, b in zip(calls.t0[w0:w1 + 1], calls.t0[w0 + 1:])),
            reverse=True)[:3],
        "flops_per_timestep": per_timestep(cfg),
    }
    harness.eprint(
        f"set-up: total_s={setup_s:.3f} " + " ".join(
            f"{k}={v:.3f}" if isinstance(v, float) else f"{k}={v}"
            for k, v in setup.items())
        + f" cache_hits={clock.cache_hits} programs={clock.programs}")
    harness.eprint("window: " + " ".join(f"{k}={v}" for k, v in
                                        counts.items()))
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
        "serve_peak_bytes_per_stream": (mem_peak / lanes, "B"),
    }

    # ---- correctness: free the fleet, then replay a sample -----------------
    active = {s.sid: slot for slot, s in enumerate(sched.grid.occupant)
              if s is not None}
    chosen = _sample(sessions, lanes, mix, seed)
    program = []
    for sid in chosen:
        s, slot = sessions[sid], active.get(sid)
        held = slot is not None
        program.append({
            "sid": sid, "pops": s.pops,
            "logits": [p.logits for p in s.predictions],
            "delta": (np.asarray(sched.deltas[slot]) if held
                      else s.final_deltas),
            "state": (jax.tree_util.tree_map(lambda a: np.asarray(a[slot]),
                                             sched.state) if held else None),
        })
    spans = tracer.spans() if tracer is not None else []
    sched.close()
    del sched
    t = time.perf_counter()
    checks = serve_checks(cfg, params, traffic, program, seed,
                          control=control)
    harness.eprint(f"reference: streams={len(program)} "
                   f"seconds={time.perf_counter() - t:.3f}")

    return {
        "metrics": metrics, "attempted": attempted, "failed": failed,
        "checks": checks, "peak_bytes": mem_peak, "counts": counts,
        "spans": spans, "window": (t0, t1), "profile": prof,
    }


def _sample(sessions, lanes, mix, seed):
    """Sessions compared, drawn from the seed: ``compare_streams`` of those
    that delivered a window, half of them (where there are any) among the
    replacements admitted after the initial fleet."""
    rng = np.random.default_rng([seed, 3])
    k = mix["compare_streams"]
    done = [sid for sid, s in sessions.items() if len(s.predictions)]
    late = [sid for sid in done if sid >= lanes]
    early = [sid for sid in done if sid < lanes]
    n_late = min(len(late), k // 2)
    pick = list(rng.choice(late, n_late, replace=False)) if n_late else []
    pick += list(rng.choice(early, min(len(early), k - n_late),
                            replace=False))
    return sorted(int(p) for p in pick)
