#!/usr/bin/env python3
"""Read the numbers that ``correct`` compares, over many runs of one cell
in one process, to set and check the limits (``PERF.md`` gives them).

    python3 bench/readings.py --workload serve_steady --seconds 5 \\
        --seeds 7001 7002 ... --control high \\
        --faults state_unchanged bf16_weights --fault-seeds 7001 7002 7003 \\
        --out chiprun_out/readings_serve_steady.jsonl

Each seed runs the cell once with the control beside the program (the
program's readings and the control's, from one run); each fault then runs
once on each fault seed. One JSON line per run goes to standard output
and to ``--out``. The benchmark's own runs never use this script; it
shares their set-up and comparison through :func:`bench.run.run_cell`,
so it needs the chip the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
# Without this the TPU runtime first asks a cloud metadata server for the
# host's topology; a host with its chips attached and no such server then
# waits on the query, for seconds or for good, before the chip comes up.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

from bench import compare, harness  # noqa: E402
from bench.run import run_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control", default="")
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    runs = [(s, "", args.control) for s in args.seeds]
    runs += [(s, f, "") for f in args.faults for s in args.fault_seeds]
    out = open(args.out, "a") if args.out else None
    try:
        for seed, fault, control in runs:
            t = time.perf_counter()
            try:
                line, checks = run_cell(cell, seed=seed,
                                        seconds=args.seconds, trace=False,
                                        fault=fault, control=control,
                                        t_start=t)
            except harness.NoChip as e:
                harness.eprint(f"bench/readings.py: {e}")
                return 2
            res = json.loads(line)
            rec = {"workload": cell.name, "seed": seed, "fault": fault,
                   "control": control, "correct": res["correct"],
                   "attempted": res["attempted"], "failed": res["failed"],
                   "checks": {c.name: c.value for c in checks},
                   "readings": dict(compare.last_readings),
                   "seconds": time.perf_counter() - t}
            text = json.dumps(rec)
            print(text, flush=True)
            if out is not None:
                out.write(text + "\n")
                out.flush()
    finally:
        if out is not None:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
