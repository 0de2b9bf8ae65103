#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``), its traffic
(``bench/traffic/<mix>.json``) and its metrics come from
``BENCHMARK.json``. The run makes its weights and inputs from ``--seed``,
sets up and warms the cell's own shapes, measures for ``--seconds``, then
checks what the timed path produced against the plain reference.

* ``--trace 0`` reports the cell's end-to-end metrics, tracing off.
* ``--trace 1`` turns on the scheduler's spans as profiler annotations and
  the JAX profiler over a steady stretch of the window, and reports the
  cell's per-layer metrics (``bench/metrics/<metric>.py``), the device's
  busy and window seconds, and a breakdown.

Set-up, window counts and the numbers compared go to standard error; the
numbers compared, each beside its limit, are its last lines. The last line
of standard output is the result, one JSON object. With no TPU, fewer
chips than the cell needs, or a device kind without published peaks
(``bench/peaks.py``), the command exits 2 and prints no result.

The limits are read with ``bench/readings.py``, which drives
:func:`run_cell` with the control (the reference, computed at a lower
matmul precision, in the program's place) or a planted fault
(``bench/faults.py``).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from typing import Optional

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# Without this the TPU runtime first asks a cloud metadata server for the
# host's topology; a host with its chips attached and no such server then
# waits on the query, for seconds or for good, before the chip comes up.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

from bench import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, fault: str = "", control: str = "",
             t_start: float = T_START,
             cache_dir: Optional[str] = harness.CACHE_DIR):
    """Run ``cell`` once; returns ``(result line, checks)``. Raises
    :class:`harness.NoChip` when the devices do not fit the cell.
    ``require_tpu=False`` and ``cache_dir=None`` let the tests drive a
    small cell on the CPU without the chip's checks or the cache."""
    t = time.perf_counter()
    import jax
    from bench import peaks
    if cache_dir:
        harness.use_compile_cache(cache_dir)
    t_jax = time.perf_counter()
    devs = harness.check_devices(cell.chips, require_tpu)
    harness.eprint(f"start-up: before_jax_s={t - t_start:.3f} "
                   f"jax_import_s={t_jax - t:.3f} "
                   f"backend_init_s={time.perf_counter() - t_jax:.3f}")
    peak = None
    if require_tpu:
        try:
            peak = peaks.peak(devs[0].device_kind)
        except KeyError as e:
            raise harness.NoChip(str(e)) from e
    clock = harness.CompileClock()
    with jax.default_matmul_precision(cell.config["precision"]):
        out = harness.mode_runner(cell)(
            cell, seed=seed, seconds=seconds, trace=trace, clock=clock,
            t_start=t_start, devs=devs, fault=fault, control=control)
    checks = out["checks"]
    attempted, failed = out["attempted"], out["failed"]
    correct = failed == 0 and attempted > 0 and all(c.ok for c in checks)
    device = harness.device_record(devs, out["peak_bytes"])
    breakdown = None
    if trace:
        metrics = {}
        prof = out["profile"]
        red = prof.reduced if prof is not None else None
        t0, t1 = out["window"]
        ctx = harness.Context(
            counts=out["counts"],
            spans=[s for s in out["spans"] if t0 <= s.t0_s <= t1],
            trace=red, peak=peak, chips=len(devs))
        for m in cell.per_layer:
            v = harness.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = (v, m["unit"])
        if red is not None:
            device["busy_s"] = red.busy_s
            device["window_s"] = red.window_s
            breakdown = {"device_ops": [[k, v] for k, v in red.top_ops],
                         "idle_gaps": [[k, v] for k, v in red.idle_gaps]}
            harness.eprint(
                "trace: window_s=%r busy_s=%r programs=%r" %
                (red.window_s, red.busy_s, red.programs))
    else:
        metrics = {m["name"]: out["metrics"][m["name"]]
                   for m in cell.end_to_end}
    harness.eprint(f"result: attempted={attempted} failed={failed} "
                   f"correct={correct}")
    harness.eprint("checks: " + harness.checks_line(checks))
    for c in checks:
        harness.eprint(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
                       f"{'ok' if c.ok else 'FAILED'}")
    line = harness.result_line(correct=correct, attempted=attempted,
                               failed=failed, metrics=metrics,
                               device=device, checks=checks,
                               breakdown=breakdown)
    return line, checks


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.find_cell(args.workload)
    try:
        line, _ = run_cell(cell, seed=args.seed, seconds=args.seconds,
                           trace=bool(args.trace))
    except harness.NoChip as e:
        harness.eprint(f"bench/run.py: {e}")
        return 2
    sys.stdout.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
