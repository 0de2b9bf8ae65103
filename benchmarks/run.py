"""Benchmark harness: one module per paper table/figure + the roofline.

``PYTHONPATH=src python -m benchmarks.run [--full]`` prints
``name,us_per_call,derived`` CSV. ``--json out.json`` additionally
writes a machine-readable results artifact (schema below) so successive
runs accumulate a benchmark trajectory instead of scrolling away.
Modules:

  fig3  — async SerDes functional stand-in (packing/delay buffer)
  fig4  — OSSL ablations (PC/CC/depth/WU-locking)
  fig5  — DSST factorized sorting + accuracy restoration
  fig6  — input-stationary sparse forward path
  fig7  — five tasks: accuracy + modeled µW vs paper numbers, + depth sweep
  table1— memory cut / NCE / headline ratios
  serving — concurrent event-stream serving: throughput/latency/energy,
            incl. live-topology-evolution vs frozen baseline and the
            hot-path A/B (the module's --evolve / --pipeline / --factors
            CLI modes run the focused sweeps; --dryrun lists them)
  backend — engine backend seam: ref vs pallas-interpret step + parity
  roofline — per-(arch×shape×mesh) terms from dry-run artifacts (if present)

``--dryrun`` only verifies every module imports and registers a ``run``
callable — the CI smoke step that keeps registration from rotting.

Compiled programs persist across runs in JAX's compilation cache: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else
``<repo>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ARTIFACT_SCHEMA = "repro-bench/1"


def write_artifact(path: str, rows: list, *, failed: int = 0,
                   argv=None, contracts_checked=None) -> dict:
    """Write the ``--json`` results artifact; returns the document.

    Schema ``repro-bench/1``: top-level ``schema``/``created_unix_s``/
    ``argv``/``failed``/``contracts_checked`` plus ``rows`` — each row
    carries the CSV triple (``name``, ``us_per_call``, ``derived``)
    verbatim and, when a module attached them, structured extras:
    ``metrics`` (a flat dict of derived numbers, e.g. the serving rows'
    overlap ratio and per-phase p50/p99) and ``obs`` (a
    ``MetricsRegistry.snapshot()`` of the run). ``contracts_checked`` is
    ``repro.analysis.registry.summary()`` — which entrypoint contract
    sets held when the numbers were taken (``None`` if the registry
    could not run).
    """
    doc = {
        "schema": ARTIFACT_SCHEMA,
        "created_unix_s": time.time(),
        "argv": list(sys.argv if argv is None else argv),
        "failed": int(failed),
        "contracts_checked": contracts_checked,
        "rows": [{
            "name": r["name"],
            "us_per_call": float(r["us_per_call"]),
            "derived": str(r["derived"]),
            **({"metrics": r["metrics"]} if "metrics" in r else {}),
            **({"obs": r["obs"]} if "obs" in r else {}),
        } for r in rows],
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="longer training runs")
    ap.add_argument("--only", default="", help="comma list of module names")
    ap.add_argument("--dryrun", action="store_true",
                    help="verify benchmark registration only (CI smoke)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write a machine-readable results artifact")
    args = ap.parse_args()
    quick = not args.full

    from repro.runtime.compile_cache import use_persistent_cache
    use_persistent_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    from . import (bench_backend, bench_fig3_serdes, bench_fig4_ossl,
                   bench_fig5_dsst, bench_fig6_datapath, bench_fig7_tasks,
                   bench_kernels, bench_serving_streams, bench_table1,
                   roofline)
    modules = {
        "fig3": bench_fig3_serdes, "fig4": bench_fig4_ossl,
        "fig5": bench_fig5_dsst, "fig6": bench_fig6_datapath,
        "fig7": bench_fig7_tasks, "table1": bench_table1,
        "kernels": bench_kernels, "serving": bench_serving_streams,
        "backend": bench_backend, "roofline": roofline,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    if args.dryrun:
        bad = [k for k, m in modules.items()
               if not callable(getattr(m, "run", None))]
        for k in sorted(modules):
            status = "BROKEN" if k in bad else "REGISTERED"
            # modules with focused CLI modes advertise them (CLI_FLAGS) so
            # the dryrun doubles as the flag index — e.g. serving lists its
            # --devices / --evolve / --pipeline / --factors A/B sweeps
            flags = getattr(modules[k], "CLI_FLAGS", "")
            print(f"{k},0.00,{status}" + (f" {flags}" if flags else ""))
        if bad:
            sys.exit(1)
        return

    print("name,us_per_call,derived")
    failed = 0
    collected = []
    for key, mod in modules.items():
        try:
            for row in mod.run(quick=quick):
                print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
                collected.append(row)
        except Exception:
            failed += 1
            print(f"{key},0.00,ERROR", file=sys.stdout)
            traceback.print_exc(file=sys.stderr)
            collected.append({"name": key, "us_per_call": 0.0,
                              "derived": "ERROR"})
    if args.json:
        # stamp the artifact with the contract-registry result: benchmark
        # numbers only mean something if the hot path's structural
        # invariants held when they were taken
        try:
            from repro.analysis import registry as _registry
            contracts = _registry.summary()
        except Exception:
            traceback.print_exc(file=sys.stderr)
            contracts = None
        # written even on partial failure (failed > 0 is recorded in the
        # artifact) so a flaky module never costs the whole trajectory point
        write_artifact(args.json, collected, failed=failed,
                       contracts_checked=contracts)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
