"""From a JAX profiler trace (``.xplane.pb``) to the device numbers.

What it reads, on the profiler's own clock:

* the traced window: the host annotation ``bench.traced`` that the
  benchmark opens right after the profiler starts and closes right before
  it stops;
* device busy time: the union of the intervals of the ``XLA Ops`` events
  of each ``/device:TPU:<n>`` plane inside the window (``XLA Modules``
  where a plane has no op line), averaged over the chips;
* device time per program: ``XLA Modules`` events by program name, the
  ``(id)`` suffix dropped (``jit_chunk_fn(42)`` -> ``jit_chunk_fn``);
* the device operations that took most time, and the longest idle gaps,
  each gap named by the innermost host annotation open at its middle
  (the scheduler's spans when its tracer annotates).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.traced"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                               # mean over device planes
    programs: Dict[str, Tuple[float, int]]      # name -> (seconds, runs)
    top_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    n_devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, prefix: str) -> Optional[Tuple[float, int]]:
        """Total seconds and runs of the programs whose name starts with
        ``prefix`` (None when none ran)."""
        hits = [v for k, v in self.programs.items() if k.startswith(prefix)]
        if not hits:
            return None
        return sum(s for s, _ in hits), sum(n for _, n in hits)


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def reduce_file(path: str, top: int = 10) -> Reduced:
    """Reduce one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.start_ns, ev.end_ns, ev.name))
    win = [(a, b) for a, b, n in host if n == WINDOW]
    if not win:
        raise ValueError(f"{path}: no '{WINDOW}' annotation in the trace")
    lo, hi = win[0]
    busy_total, programs, ops = 0.0, {}, {}
    busy_iv: List[Tuple[float, float]] = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        iv = []
        if op_line is not None:
            for ev in op_line.events:
                a, b = _clip(ev.start_ns, ev.end_ns, lo, hi)
                if b > a:
                    iv.append((a, b))
                    if op_line.name == "XLA Ops":
                        name = _op_name(ev.name)
                        ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        u = _union(iv)
        busy_total += sum(b - a for a, b in u) * 1e-9
        if not busy_iv:
            busy_iv = u
        mod_line = lines.get("XLA Modules")
        if mod_line is not None:
            for ev in mod_line.events:
                if ev.start_ns >= lo and ev.end_ns <= hi:
                    name = _SUFFIX.sub("", ev.name)
                    s, n = programs.get(name, (0.0, 0))
                    programs[name] = (s + ev.duration_ns * 1e-9, n + 1)
    n_dev = max(1, len(devices))
    gaps, prev = [], lo
    for a, b in busy_iv + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    # Python-function events ("$file:line name") say less than the
    # annotations around them
    spans = sorted((a, b, n) for a, b, n in host
                   if n != WINDOW and not n.startswith("$"))
    gap_by: Dict[str, float] = {}
    active: List[Tuple[float, float, str]] = []
    i = 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (a + b)
        while i < len(spans) and spans[i][0] <= mid:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[1] >= mid]
        k = (min(active, key=lambda s: s[1] - s[0])[2] if active
             else "(no host span)")
        gap_by[k] = gap_by.get(k, 0.0) + (b - a) * 1e-9
    return Reduced(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total / n_dev,
        programs=programs,
        top_ops=sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(gap_by.items(), key=lambda kv: -kv[1])[:top],
        n_devices=len(devices))


def find_xplane(root: str) -> str:
    hits = glob.glob(os.path.join(root, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return max(hits, key=os.path.getmtime)


class Profile:
    """The profiler over the last ``span_s`` seconds of a measured window.

    :meth:`tick` is called with the seconds elapsed before each step of
    the window and starts the profiler once ``seconds - span_s`` have
    passed; :meth:`stop`, called once the window has closed, stops it and
    reduces the trace — so neither the trace's collection nor its
    reduction falls inside the window."""

    def __init__(self, seconds: float, span_s: float):
        self.start = max(0.0, seconds - span_s)
        self.dir = None
        self._ann = None
        self.reduced: Optional[Reduced] = None

    def tick(self, elapsed: float) -> None:
        import jax
        if self.dir is None and elapsed >= self.start:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # annotations, not every call
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._ann = jax.profiler.TraceAnnotation(WINDOW)
            self._ann.__enter__()

    def stop(self) -> None:
        import jax
        if self._ann is None:
            return
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()
        try:
            self.reduced = reduce_file(find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
