"""What every cell shares: the manifest, the lookup of a cell's files by
name, the compile cache and clock, and the result line.

A cell is one entry of ``BENCHMARK.json``'s ``workloads``. Its files are
found by name, so a later change adds a cell, a configuration, a traffic
mix, a mode or a per-layer metric by adding files and entries:

* ``bench/configs/<config>.json`` — the configuration as it is run; its
  ``mode`` names the runner, ``bench/modes/<mode>.py``;
* ``bench/traffic/<traffic>.json`` — the mix's parameters, read by
  ``bench/generator.py``;
* ``bench/metrics/<metric>.py`` — one reader per per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
from typing import Any, Callable, Dict, List, Optional, Tuple

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]          # the configuration file's contents
    traffic_name: str
    traffic: Dict[str, Any]         # the mix's parameters
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    run_seconds: int


def load_manifest(root: str = ROOT) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _in_cell(metric: Dict[str, Any], cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find_cell(name: str, root: str = ROOT) -> Cell:
    """Resolve a workload name to its configuration, traffic and metrics."""
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    e2e = [m for m in man["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in man["per_layer"] if _in_cell(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer,
                run_seconds=int(man["run_seconds"]))


def load_module(path: str, name: str):
    """Import a file of the benchmark by path (names may hold dots)."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def mode_runner(cell: Cell) -> Callable:
    return importlib.import_module("bench.modes." + cell.config["mode"]).run


def metric_reader(name: str) -> Callable:
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_")).read


# ---------------------------------------------------------------------------
# compile cache and compile clock
# ---------------------------------------------------------------------------

def use_compile_cache(path: str = CACHE_DIR) -> str:
    """JAX's persistent cache at a fixed path inside the checkout, for every
    program however short its compile (the eager lane-surgery programs of
    admission compile in well under JAX's default 1 s floor)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compile seconds, program count and persistent-cache hits of
    the whole process, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GcClock:
    """Seconds the interpreter spent in cyclic garbage collection, and how
    many collections of the oldest generation ran, from the end of set-up
    until :meth:`stop`."""

    def __init__(self):
        import gc
        import time
        self.seconds, self.full, self._t0 = 0.0, 0, None
        self._time = time.perf_counter
        gc.callbacks.append(self._cb)

    def stop(self) -> None:
        """Stop counting and undo :func:`settle`."""
        import gc
        gc.callbacks.remove(self._cb)
        gc.unfreeze()

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = self._time()
        elif self._t0 is not None:
            self.seconds += self._time() - self._t0
            self.full += info.get("generation") == 2
            self._t0 = None


def settle() -> None:
    """End of set-up: collect, then move everything set-up made to the
    permanent generation, so that the window's collections scan only what
    the window makes (as a long-running server does after start-up)."""
    import gc
    gc.collect()
    gc.freeze()


# ---------------------------------------------------------------------------
# device and result
# ---------------------------------------------------------------------------

class NoChip(RuntimeError):
    """No accelerator of the kind or count the cell needs."""


def check_devices(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX's first device is "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def device_record(devs, peak_bytes: int) -> Dict[str, Any]:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip, where the backend says."""
    out = 0
    for d in devs:
        st = d.memory_stats() or {}
        out = max(out, int(st.get("peak_bytes_in_use", 0)))
    return out


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read: the run's counts, the
    program's spans inside the window, the reduced trace (None where the
    run took none) and the chip's peaks (None off the chip)."""
    counts: Dict[str, Any]
    spans: List[Any]
    trace: Any
    peak: Optional[Dict[str, Any]]
    chips: int

    def spans_named(self, name: str) -> List[Any]:
        return [s for s in self.spans if s.name == name]

    def children(self, parent, name: str) -> List[Any]:
        return [s for s in self.spans
                if s.parent_id == parent.span_id and s.name == name]


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, with its limit (upper)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def checks_line(checks: List[Check]) -> str:
    return " ".join(f"{c.name}={c.value!r}(limit {c.limit!r})"
                    for c in checks)


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Tuple[float, str]], device: Dict,
                checks: List[Check], breakdown=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed),
           "metrics": {k: {"value": v, "unit": u}
                       for k, (v, u) in metrics.items()},
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return json.dumps(out)
