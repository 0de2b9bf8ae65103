"""Per-stream stateful SNN sessions.

A ``StreamSession`` is the host-side record of one event stream: its
identity, lifecycle status, buffered-but-unprocessed event chunks, emitted
window predictions, and accumulated per-stream telemetry. The *device*-side
state (membrane potentials, the three-trace neuron SRAM, per-stream gate
thresholds, per-stream weight deltas) lives in batched pytrees whose leading
axis is the slot index — sessions only remember *which lane* is theirs.

Lane surgery touches exactly the lanes it names and leaves every other
stream's lane bit-identical. Admission resets all of a stage's claimed
lanes at once with the program :func:`make_reset_lanes` builds: one
donated, in-place write of fresh values into those lanes. ``write_lane`` /
``read_lane`` / ``fresh_lane_state`` are the single-lane reference
(tree-maps over one slot index) that the isolation tests pin it against.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Any, List, Optional

import jax
import numpy as np

from repro.core.snn import (SNNConfig, init_stream_deltas, init_stream_state)
from repro.launch import sharding


class SessionStatus(enum.Enum):
    QUEUED = "queued"
    ACTIVE = "active"
    RETIRED = "retired"


@dataclasses.dataclass
class WindowPrediction:
    """Readout emitted when a session's T-step window closes."""
    window_idx: int
    logits: np.ndarray        # [n_out]

    @property
    def label(self) -> int:
        return int(np.argmax(self.logits))


@dataclasses.dataclass
class StreamSession:
    sid: int
    source: Any = None                      # StreamSource (stream_source.py)
    adapt: bool = True                      # OSSL adaptation on for this stream
    n_in: Optional[int] = None              # event width; learned on first
    #   push, or stamped by the scheduler at submit — keeps pop_chunk's
    #   empty result a well-shaped [0, n_in] (not a [0, 0] broadcast trap)
    tier: Optional[str] = None              # QoS tier; resolved at submit
    status: SessionStatus = SessionStatus.QUEUED
    slot: Optional[int] = None
    timesteps_fed: int = 0
    predictions: List[WindowPrediction] = dataclasses.field(default_factory=list)
    # buffered events that arrived but have not been stepped yet
    _pending: List[np.ndarray] = dataclasses.field(default_factory=list)
    # the IngestWorker holding this session's queued-but-undrained chunks
    # (set by IngestWorker.attach, cleared at detach); consulted by
    # ``exhausted`` so lookahead polling cannot retire a stream early
    _ingest: Any = None
    # per-stream snapshot of deltas captured at retire (for inspection or
    # for promoting a stream's adaptation into the shared base); stacked in
    # the fleet's delta layout — compact [n_layers, J, T, bk, bo] on the
    # default hot path, dense [n_layers, Kmax, n_hidden] for dense fleets
    # (engine.densify_deltas converts when a dense view is needed)
    final_deltas: Optional[np.ndarray] = None

    # -- event buffering -----------------------------------------------------
    def push_events(self, chunk: np.ndarray) -> None:
        """chunk: [c, n_in] binary spikes, any c >= 1."""
        if chunk.ndim != 2:
            raise ValueError(f"chunk must be [c, n_in], got {chunk.shape}")
        if self.n_in is None:
            self.n_in = int(chunk.shape[1])
        elif chunk.shape[1] != self.n_in:
            raise ValueError(
                f"chunk width {chunk.shape[1]} != session n_in {self.n_in}")
        self._pending.append(np.asarray(chunk, np.float32))

    def pending_timesteps(self) -> int:
        """Buffered-but-unprocessed timesteps across all pending chunks."""
        return sum(c.shape[0] for c in self._pending)

    def pop_chunk(self, max_len: int) -> np.ndarray:
        """Pop up to ``max_len`` buffered timesteps as one [c, n_in] array."""
        out, need = [], max_len
        while self._pending and need > 0:
            head = self._pending[0]
            if head.shape[0] <= need:
                out.append(self._pending.pop(0))
                need -= head.shape[0]
            else:
                out.append(head[:need])
                self._pending[0] = head[need:]
                need = 0
        if not out:
            return np.zeros((0, self.n_in or 0), np.float32)
        return np.concatenate(out, axis=0)

    @property
    def exhausted(self) -> bool:
        """True when the source has ended and no buffered events remain —
        neither here in ``_pending`` nor queued in the ingest worker.

        The ingest check closes the EOS-exactly-once hole async polling
        opens: the worker polls ahead of the grid, so ``source.exhausted``
        can flip while the tail chunk still sits in the worker's queue
        (stamped for a future tick). Without it the scheduler would
        retire the session that step and the tail would never be fed
        (the lost-tail / double-retire regression in
        tests/test_serving_qos.py).
        """
        src_done = self.source is None or self.source.exhausted
        queued = self._ingest is not None and self._ingest.has_pending(self.sid)
        return src_done and not queued and not self._pending


# ---------------------------------------------------------------------------
# lane surgery over the batched device pytrees
# ---------------------------------------------------------------------------

def write_lane(batched, single, slot: int):
    """Write ``single`` (same pytree, leading axis 1) into lane ``slot`` of
    the slot-leading ``batched`` pytree; every other lane's bits are
    untouched. Returns the new pytree (leaves are fresh arrays —
    ``.at[].set`` never mutates)."""
    return jax.tree_util.tree_map(
        lambda b, s: b.at[slot].set(s[0]), batched, single)


def read_lane(batched, slot: int):
    """Extract lane ``slot`` of every leaf of a slot-leading pytree,
    keeping a leading axis of 1 (the shape ``write_lane`` expects back)."""
    return jax.tree_util.tree_map(lambda b: b[slot:slot + 1], batched)


def fresh_lane_state(cfg: SNNConfig, compact: bool | None = None):
    """A 1-slot initial ``(StreamState, deltas)`` pair: what admission
    resets a claimed lane to (fresh traces, zero delta; ``compact``
    selects the delta layout, None = auto)."""
    return init_stream_state(cfg, 1), init_stream_deltas(cfg, 1,
                                                         compact=compact)


def make_reset_lanes(cfg: SNNConfig, compact: bool | None = None,
                     state_sh=None, delta_sh=None):
    """Build ``reset_lanes(state, deltas, slots) -> (state, deltas)``: one
    jitted program that resets every lane named in ``slots`` to its
    initial value, in place.

    ``state``/``deltas`` are the slot-leading grids and are donated: the
    caller must drop every other handle to them first. ``slots`` is a
    fixed-length ``[S]`` int32 vector, the admitted lanes first and then
    ``S`` (out of range) as padding, so one compile serves any number of
    admitted lanes. The program loops over the admitted lanes and writes
    each one's fresh value over it, touching no other lane and holding no
    second copy of the grid. The fresh values are
    :func:`fresh_lane_state`'s, built inside the program: nothing is sent
    from the host and no lane is allocated per session.
    ``state_sh``/``delta_sh``, the grids' slot shardings under a mesh,
    become the program's in and out shardings, so the reset keeps them
    (each device writes only the admitted lanes it holds, with no
    collective). ``reset_lanes.n_traces()`` counts its traces.
    """
    traces = {"n": 0}
    jit_kw = {}
    if state_sh is not None:
        rep = sharding.replicated(delta_sh.mesh)
        jit_kw = {"in_shardings": (state_sh, delta_sh, rep),
                  "out_shardings": (state_sh, delta_sh)}

    @functools.partial(jax.jit, donate_argnums=(0, 1), **jit_kw)
    def reset_lanes(state, deltas, slots):
        traces["n"] += 1
        fresh = fresh_lane_state(cfg, compact=compact)

        def reset_one(i, grid):
            return jax.tree_util.tree_map(
                lambda b, f: jax.lax.dynamic_update_slice_in_dim(
                    b, f, slots[i], 0), grid, fresh)

        admitted = (slots < slots.shape[0]).sum()
        return jax.lax.fori_loop(0, admitted, reset_one, (state, deltas))

    reset_lanes.n_traces = lambda: traces["n"]
    return reset_lanes


def nbytes(tree) -> int:
    """Summed ``nbytes`` of a pytree's array leaves: shape arithmetic, no
    device sync (what the scheduler's byte counts add up)."""
    return sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(tree))
