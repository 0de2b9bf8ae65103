"""Double-buffered event staging for the serving hot path.

The grid step used to be strictly serial per step: pack host event buffers
→ dispatch the jitted chunk fn → block on the device → fetch metrics →
bookkeep.  Host packing and device compute each sat idle while the other
ran.  This module is the pipelining half of the fix (the other half is the
static ``want_factors`` seam in ``adapt.make_chunk_fn``): the scheduler's
step is split into three explicit phases —

* **stage**   — host work: advance the virtual clock, poll sources, admit
  queued sessions (enqueueing one lane-reset program, never waiting on
  the device), pack the ``[C, S, ceil(n_in/8)]`` packed-bit event /
  ``[C, S]`` valid buffers, and *decide* which sessions will exhaust
  after this step (a pure host fact: source done + pending buffer
  drained).  Produces a :class:`StagedChunk`.
* **dispatch** — enqueue the chunk fn on the staged buffers and return
  immediately (JAX dispatch is asynchronous); the device handles plus the
  staged host record become an :class:`InFlight` step.
* **retire**  — consume one in-flight step's results: fetch its metrics
  (this is the only point that waits on the device), route window
  predictions, fold telemetry, finalize retiring sessions from the
  *captured* output handles, and feed/drive the topology service.

With ``depth=0`` the three phases run back-to-back inside one ``step()``
— bit-identical to the pre-pipeline scheduler.  With ``depth>=1``
(:class:`StagingPipeline` holds the in-flight steps) the stage phase for
grid step ``t+1`` runs **while the device computes step t**, exactly the
way event-driven silicon (ElfCore's async SerDes front-end, ReckOn's
spike buffers) hides I/O behind compute.  The in-flight record's
``metrics`` handles are never touched by later stages; admission's lane
reset donates the live delta grid, so before it runs the scheduler moves
the retiring lanes' final deltas of any in-flight step that still holds
that grid into per-lane slices (``InFlight.take_snapshots``).  Deferred
bookkeeping therefore reads exactly the values the step produced — the
pipeline changes *when* host work happens, never *what* the device
computes.  Pipeline-on and pipeline-off trajectories are
pinned bit-identical (1-device and 8-device) in
``tests/test_serving_pipeline.py``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional, Tuple


@dataclasses.dataclass
class LaneRecord:
    """What one occupied lane was fed this grid step (host-side facts that
    the retire phase pairs with the device metrics)."""
    slot: int
    session: Any                 # StreamSession
    n_fed: int                   # timesteps packed into the lane


@dataclasses.dataclass
class StagedChunk:
    """One grid step's host-assembled inputs + scheduling decisions.

    ``events [C, S, ceil(n_in/8)]`` (packed spike bits) / ``valid [C, S]``
    / ``adapt_mask [S]`` are the chunk fn's staging buffers.  ``retiring``
    lists the ``(slot, session)`` pairs that exhaust after this step —
    known at stage time, finalized at retire time.  ``merge_slots``
    snapshots the adaptive occupants eligible for a hot-stream fold should
    a topology epoch run after this step (captured here so a pipelined
    retire sees the same candidate set the serial scheduler would —
    admissions from *later* stage phases must not leak into an earlier
    step's epoch).

    ``slots`` (the occupied lanes, in ``lanes`` order), ``spikes`` (each
    lane's packed spike count, ``[S]``) and ``rows`` (each occupied lane's
    row of the telemetry's counter table, gathered at stage, so a lane
    re-admitted before this step retires still folds into its old
    stream) are what retire folds the step's counters from, as arrays.
    """
    events: Any                  # np.ndarray [C, S, ceil(n_in/8)] uint8
    valid: Any                   # np.ndarray [C, S] bool
    adapt_mask: Any              # np.ndarray [S] bool
    lanes: List[LaneRecord]
    retiring: List[Tuple[int, Any]]
    merge_slots: Tuple[int, ...]
    fed: Dict[int, int]          # {slot: timesteps fed} (step() return value)
    slots: Any = None            # np.ndarray [n_occupied] intp
    spikes: Any = None           # np.ndarray [S] int
    rows: Any = None             # np.ndarray [n_occupied] intp


@dataclasses.dataclass
class InFlight:
    """A dispatched-but-unretired grid step: the staged host record plus
    the chunk fn's (asynchronous) output handles.  ``deltas`` is captured
    at dispatch, so retiring sessions snapshot their final adaptation even
    if a later admit has already reset that lane on the live arrays.

    Admission's reset program donates the live delta grid, which may be
    this very handle: before it runs, :meth:`take_snapshots` slices the
    retiring lanes off the grid into ``snapshots`` and drops ``deltas``
    (retire does the same, for a step whose grid was never donated)."""
    staged: StagedChunk
    deltas: Any                  # slot-leading delta handle (post-step); compact [S, L, J, T, bk, bo] or dense [S, L, Kmax, N]; None once snapshotted
    metrics: Any                 # ChunkMetrics device handles
    grid_step: int               # grid.stats["steps"] after this step's tick
    # {slot: final-delta device handle} of the staged retiring lanes
    snapshots: Optional[Dict[int, Any]] = None
    # host/device overlap bookkeeping (stamped by StagingPipeline push/pop;
    # both stay 0.0 on the serial depth=0 path, which never enqueues)
    pushed_at: float = 0.0       # perf_counter when the step entered the queue
    queued_s: float = 0.0        # time in flight before retire began

    def take_snapshots(self) -> None:
        """Slice each retiring lane's final deltas off the captured grid
        (eager device slices, no host wait) and drop the grid handle;
        idempotent."""
        if self.snapshots is None:
            self.snapshots = {slot: self.deltas[slot]
                              for slot, _ in self.staged.retiring}
            self.deltas = None


class StagingPipeline:
    """Bounded FIFO of in-flight grid steps (the double buffer).

    ``depth`` is the number of dispatched steps that may be outstanding
    before the scheduler must retire the oldest:

    * ``0`` — synchronous: every step retires before ``step()`` returns
      (the reference behavior; still runs through the same three phases).
    * ``1`` — double buffering: step ``t+1`` is staged while step ``t``
      computes.  The sweet spot: host packing is hidden, and a topology
      epoch due after step ``t`` still lands before step ``t+1`` is
      dispatched, which is what keeps evolving fleets bit-identical to
      the synchronous path.
    * ``>1`` — deeper queues additionally hide retire-phase host
      bookkeeping, but defer an epoch past already-dispatched steps — the
      scheduler therefore clamps depth to 1 when a live topology service
      is attached.
    """

    def __init__(self, depth: int = 1):
        if depth < 0:
            raise ValueError(f"pipeline depth must be >= 0, got {depth}")
        self.depth = depth
        self._q: Deque[InFlight] = deque()

    def set_depth(self, depth: int) -> None:
        """Resize the pipeline at a drain-safe boundary (the adaptive-depth
        autopilot's apply point). Refuses while steps are in flight —
        shrinking under a loaded queue would strand bookkeeping, and the
        bit-identity argument for adaptive depth rests on every resize
        happening against an empty pipeline (flush first)."""
        if depth < 0:
            raise ValueError(f"pipeline depth must be >= 0, got {depth}")
        if self._q:
            raise RuntimeError(
                f"cannot resize with {len(self._q)} step(s) in flight — "
                "flush the pipeline first (depth changes land only at "
                "drain-safe boundaries)")
        self.depth = depth

    def __len__(self) -> int:
        return len(self._q)

    def __iter__(self) -> Iterator[InFlight]:
        """The in-flight steps, oldest first."""
        return iter(self._q)

    @property
    def full(self) -> bool:
        """True when a dispatch must be preceded by retiring the oldest."""
        return len(self._q) >= max(self.depth, 1)

    def push(self, fl: InFlight) -> None:
        if self.depth == 0:
            raise RuntimeError("synchronous pipeline (depth=0) cannot hold "
                               "in-flight steps; retire immediately instead")
        if self.full:
            raise RuntimeError("staging pipeline full; retire first")
        if hasattr(fl, "pushed_at"):
            fl.pushed_at = time.perf_counter()
        self._q.append(fl)

    def pop(self) -> InFlight:
        """Oldest in-flight step (FIFO — retire order is dispatch order).

        Stamps ``queued_s`` — how long the step was in flight while the
        host kept working (staging later steps). Paired with the retire
        phase's measured device wait, this yields the per-step host/device
        **overlap ratio** ``queued / (queued + wait)``: ~1 host-bound,
        ~0 device-bound (see ``FleetTelemetry.record_overlap``).
        """
        fl = self._q.popleft()
        if hasattr(fl, "pushed_at") and fl.pushed_at:
            fl.queued_s = time.perf_counter() - fl.pushed_at
        return fl
