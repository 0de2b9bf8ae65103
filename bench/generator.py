"""The one traffic generator: reads a mix's parameters (``bench/traffic/
<mix>.json``) and makes its inputs from ``--seed``.

The five synthetic event tasks follow the structure of ElfCore's
benchmarks (DVS gesture, N-MNIST, SHD keyword spotting, DEAP EEG emotion,
delayed-cue navigation): per-class spike-rate templates ``[T, n_in]``,
Poisson spikes and a per-window timing jitter of up to two timesteps. The
template logic is copied from the program's ``data/events.py`` so that a
change there cannot move the yardstick; unlike that file it seeds each
task from a stable checksum of its name, so the same ``--seed`` gives the
same inputs in every process.

Sizes never depend on the seed. Every seed gets the same multiset of
first-chunk lengths and session lengths, in another order, so the seed
changes which spikes arrive, not how much work there is.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional

import numpy as np

TASKS = ("gesture", "nmnist", "shd_kws", "eeg_emotion", "nav_cue")


def _grid(n_in: int):
    h = int(np.sqrt(n_in / 2))
    return h, n_in // h


def _fit(x: np.ndarray, n_in: int) -> np.ndarray:
    out = np.zeros((x.shape[0], n_in))
    out[:, : x.shape[1]] = x
    return out


def templates(name: str, n_in: int, t_steps: int, seed: int) -> np.ndarray:
    """Per-class spike-rate templates ``[n_classes, T, n_in]`` of a task."""
    rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
    h, w = _grid(n_in)
    t = np.arange(t_steps)
    if name == "gesture":          # moving 2-D blob, direction per class
        n_classes = 10

        def tmpl(c):
            ang = 2 * np.pi * c / n_classes
            vx, vy = np.cos(ang), np.sin(ang)
            ys, xs = np.mgrid[0:h, 0:w]
            out = np.zeros((t_steps, h * w))
            for ti in t:
                cy = (h / 2 + vy * ti * h / t_steps) % h
                cx = (w / 2 + vx * ti * w / t_steps) % w
                d2 = (ys - cy) ** 2 + (xs - cx) ** 2
                out[ti] = (0.35 * np.exp(-d2 / 6.0)).reshape(-1)
            return _fit(out, n_in)
    elif name == "nmnist":         # static prototype + saccade shifts
        n_classes = 10
        protos = rng.random((n_classes, h * w)) ** 3 * 0.4

        def tmpl(c):
            out = np.zeros((t_steps, h * w))
            img = protos[c].reshape(h, w)
            for ti in t:
                sx, sy = int(2 * np.sin(ti / 5)), int(2 * np.cos(ti / 7))
                out[ti] = np.roll(np.roll(img, sx, 0), sy, 1).reshape(-1)
            return _fit(out, n_in)
    elif name == "shd_kws":        # spectro-temporal keyword sweeps
        n_classes = 10
        starts = rng.integers(0, n_in // 2, size=(n_classes,))
        slopes = rng.uniform(-4, 4, size=(n_classes,))

        def tmpl(c):
            out = np.zeros((t_steps, n_in))
            for ti in t:
                center = int(starts[c] + slopes[c] * ti) % n_in
                idx = (np.arange(-8, 9) + center) % n_in
                out[ti, idx] = 0.35 * np.exp(-np.arange(-8, 9) ** 2 / 12.0)
            return out
    elif name == "eeg_emotion":    # band oscillations x scalp topography
        n_classes = 3
        freqs = [2.0, 5.0, 9.0]
        chan_phase = rng.uniform(0, 2 * np.pi, size=(n_in,))
        topo = rng.dirichlet(np.ones(3), size=n_in).T

        def tmpl(c):
            osc = 0.5 * (1 + np.sin(2 * np.pi * freqs[c] * t[:, None]
                                    / t_steps + chan_phase[None, :]))
            return 0.45 * topo[c][None, :] * osc
    elif name == "nav_cue":        # delayed cue -> decision
        n_classes = 2

        def tmpl(c):
            out = np.full((t_steps, n_in), 0.02)
            half = n_in // 2
            sl = slice(0, half) if c == 0 else slice(half, n_in)
            out[: t_steps // 5, sl] = 0.4
            out[-t_steps // 5:, :] = 0.1
            return out
    else:
        raise ValueError(f"unknown task {name!r}; expected one of {TASKS}")
    return np.stack([tmpl(c) for c in range(n_classes)]).astype(np.float32)


def draw_windows(tmpl: np.ndarray, rng: np.random.Generator, count: int):
    """``count`` windows of one task: (events [count, T, n_in] f32 {0,1},
    labels [count] int32)."""
    labels = rng.integers(0, tmpl.shape[0], size=(count,))
    jitter = rng.integers(-2, 3, size=(count,))
    rates = np.stack([np.roll(tmpl[c], j, axis=0)
                      for c, j in zip(labels, jitter)])
    ev = (rng.random(rates.shape, dtype=np.float32) < rates)
    return ev.astype(np.float32), labels.astype(np.int32)


def spread(lo: int, hi: int, count: int, rng: np.random.Generator):
    """``count`` whole numbers spread evenly over ``[lo, hi]`` (the same
    multiset for every seed), shuffled by ``rng``."""
    vals = lo + (np.arange(count) * (hi - lo + 1)) // max(count, 1)
    return rng.permutation(vals).astype(np.int64)


# ---------------------------------------------------------------------------
# serving: a fleet of streams replaying pre-drawn windows
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StreamPlan:
    """One session's traffic: its task, its windows (indices into that
    task's pool; ``None`` = endless), and the length of its first chunk."""
    sid: int
    task: str
    first_chunk: int
    n_windows: Optional[int]
    window_ids: np.ndarray       # pool indices, cycled when endless


class ServeTraffic:
    """Pools of windows per task and the plan of every session, initial
    and replacements, drawn from the seed."""

    def __init__(self, mix: Dict, cfg: Dict, lanes: int, seed: int,
                 chunk_len: int):
        self.mix, self.T, self.chunk_len = mix, cfg["t_steps"], chunk_len
        rng = np.random.default_rng([seed, 1])
        self.tasks = list(mix["tasks"])
        self.pool = {}
        for name in self.tasks:
            tm = templates(name, cfg["n_in"], cfg["t_steps"], seed)
            self.pool[name] = draw_windows(tm, rng, mix["pool_windows"])
        self._rng = rng
        lo, hi = mix["first_chunk"]
        n_plan = lanes + mix.get("replacements", 0)
        self._first = spread(lo, hi, n_plan, rng)
        self._task_of = rng.permutation(
            np.arange(n_plan) % len(self.tasks))
        init = mix.get("initial_session_windows")
        self._init_len = spread(*init, lanes, rng) if init else None
        life = mix.get("session_windows")
        self._life = (spread(*life, max(1, mix.get("replacements", 0)), rng)
                      if life else None)
        self.n_initial = lanes
        self.plans: List[StreamPlan] = []

    def next_plan(self) -> StreamPlan:
        """The next session: the first ``lanes`` are the initial fleet, the
        rest replace retired ones."""
        sid = len(self.plans)
        if sid >= len(self._first):
            raise RuntimeError(
                f"traffic plan exhausted after {sid} sessions; raise "
                f"'replacements' in the mix")
        if sid < self.n_initial:
            n = (None if self._init_len is None
                 else int(self._init_len[sid]))
        else:
            n = int(self._life[sid - self.n_initial])
        task = self.tasks[self._task_of[sid]]
        n_ids = n if n is not None else self.mix["cycle_windows"]
        ids = self._rng.integers(0, self.mix["pool_windows"], size=n_ids)
        plan = StreamPlan(sid=sid, task=task,
                          first_chunk=int(self._first[sid]), n_windows=n,
                          window_ids=ids)
        self.plans.append(plan)
        return plan

    def window(self, plan: StreamPlan, i: int) -> np.ndarray:
        """Events ``[T, n_in]`` of the plan's ``i``-th window."""
        ids = plan.window_ids
        return self.pool[plan.task][0][ids[i % len(ids)]]

    def events(self, plan: StreamPlan, n_steps: int) -> np.ndarray:
        """The first ``n_steps`` timesteps of the plan's stream."""
        n_w = -(-n_steps // self.T)
        return np.concatenate([self.window(plan, i)
                               for i in range(n_w)])[:n_steps]


class PlanSource:
    """A stream source over a :class:`StreamPlan`, kept saturated: its
    first poll releases ``first_chunk`` timesteps and every later poll one
    full chunk, so a lane always has its next chunk ready. A finite plan
    ends after its last window. ``poll(now)`` ignores the clock: the fleet
    runs closed-loop, at whatever rate the system sustains."""

    def __init__(self, traffic: ServeTraffic, plan: StreamPlan):
        self.traffic, self.plan = traffic, plan
        T = traffic.T
        self._total = None if plan.n_windows is None else plan.n_windows * T
        self._cursor = 0
        self._polls = 0

    @property
    def exhausted(self) -> bool:
        return self._total is not None and self._cursor >= self._total

    def poll(self, now: float) -> List[np.ndarray]:
        if self.exhausted:
            return []
        c = self.plan.first_chunk if self._polls == 0 \
            else self.traffic.chunk_len
        self._polls += 1
        if self._total is not None:
            c = min(c, self._total - self._cursor)
        T = self.traffic.T
        out, start = [], self._cursor
        while c > 0:
            i, off = divmod(start, T)
            take = min(c, T - off)
            out.append(self.traffic.window(self.plan, i)[off:off + take])
            start += take
            c -= take
        self._cursor = start
        return out


# ---------------------------------------------------------------------------
# training: a pool of batches across the tasks
# ---------------------------------------------------------------------------

def train_batches(mix: Dict, cfg: Dict, seed: int):
    """``mix["pool_batches"]`` batches ``(events [T, B, n_in] f32, labels
    [B] int32)``, every row a fresh draw, tasks mixed across the batch.
    Labels index the readout's ``n_out`` classes (task class ids)."""
    rng = np.random.default_rng([seed, 2])
    B, T, K = mix["batch"], cfg["t_steps"], cfg["n_in"]
    tasks = list(mix["tasks"])
    tm = {name: templates(name, K, T, seed) for name in tasks}
    out = []
    for _ in range(mix["pool_batches"]):
        which = rng.permutation(np.arange(B) % len(tasks))
        ev = np.empty((B, T, K), np.float32)
        lab = np.empty((B,), np.int32)
        for ti, name in enumerate(tasks):
            rows = np.nonzero(which == ti)[0]
            e, l = draw_windows(tm[name], rng, len(rows))
            ev[rows], lab[rows] = e, l
        out.append((np.ascontiguousarray(ev.transpose(1, 0, 2)), lab))
    return out
