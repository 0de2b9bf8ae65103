"""Faults planted under the live-DSST serving path, to show that the live
cell's ``correct`` catches them (``bench/tests/``, and on the chip
``readings.py --faults``); the benchmark's own runs plant nothing.

* ``epoch_never_swapped`` — every epoch runs and is recorded, but the
  scheduler keeps serving the base, exec rep and deltas it had;
* ``factors_one_chip`` — the chunk step hands the topology service the
  DSST factors of the first chip's lanes only (a quarter of the fleet), as
  if the cross-chip combine were left out;
* ``exec_rep_stale`` — each epoch's new base and deltas are installed but
  the chunk step keeps the old compact exec rep.
"""
from __future__ import annotations

LIVE = ("epoch_never_swapped", "factors_one_chip", "exec_rep_stale")
CHIPS = 4                    # the cell's mesh: one chip holds S/4 lanes


def plant_live(sched, fault: str) -> None:
    import jax.numpy as jnp
    if fault not in LIVE:
        raise ValueError(f"unknown live fault {fault!r}; have {LIVE}")
    if fault == "factors_one_chip":
        tier = sched._tiers[0]
        inner = tier.chunk_fn

        def chunk_fn(params, deltas, state, events, valid, amask):
            d, st, m = inner(params, deltas, state, events, valid, amask)
            first = jnp.asarray(valid).at[:, valid.shape[1] // CHIPS:].set(
                False)
            _, _, mine = inner(params, deltas, state, events, first, amask)
            return d, st, m._replace(pre_mag=mine.pre_mag,
                                     post_mag=mine.post_mag)

        chunk_fn.n_traces = inner.n_traces
        tier.chunk_fn = chunk_fn
        return
    svc = sched.topology
    enqueue = svc.enqueue

    def faulty(params, deltas, *a, **k):
        exec_params = sched._exec_params
        if fault == "exec_rep_stale":
            return enqueue(params, deltas, *a, **k)._replace(
                exec_params=exec_params)
        kept = jnp.copy(deltas)           # the grid is donated to the epoch
        return enqueue(params, deltas, *a, **k)._replace(
            params=params, exec_params=exec_params, deltas=kept)

    svc.enqueue = faulty
