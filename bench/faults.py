"""Faults planted under the timed path, to show that ``correct`` catches
them. Used by the benchmark's tests and, on the chip, to read each
fault's numbers at a cell's own size (``readings.py --faults``); the
benchmark's own runs plant nothing.

Serving (wrapping the scheduler's compiled chunk step):

* ``state_unchanged`` — the step returns the deltas and state it was given;
* ``half_batch`` — the second half of the lanes is left out of every step;
* ``answer_altered`` — every readout logit is moved by 0.05 where produced;
* ``bf16_weights`` — the base weights are rounded to bfloat16, the
  precision below the configuration's float32, on every step.

Training (wrapping the step object):

* ``state_unchanged`` — the step returns the weights it was given;
* ``half_batch`` — the step sees only the first half of each batch, so
  every batch mean is taken over the rest;
* ``answer_altered`` — the step's local loss is moved by 1 %;
* ``bf16_weights`` — the weights are rounded to bfloat16 on every step.
"""
from __future__ import annotations

SERVE = ("state_unchanged", "half_batch", "answer_altered", "bf16_weights")
TRAIN = ("state_unchanged", "half_batch", "answer_altered", "bf16_weights")


def _bf16(x):
    import jax.numpy as jnp
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def plant_serve(sched, fault: str) -> None:
    import jax.numpy as jnp
    if fault not in SERVE:
        raise ValueError(f"unknown serving fault {fault!r}; have {SERVE}")
    for tier in sched._tiers:
        inner = tier.chunk_fn

        def chunk_fn(params, deltas, state, events, valid, amask,
                     inner=inner):
            if fault == "half_batch":
                valid = jnp.asarray(valid).at[:, valid.shape[1] // 2:].set(
                    False)
            if fault == "bf16_weights":
                params = {**params, "wc": _bf16(params["wc"])}
            d, st, m = inner(params, deltas, state, events, valid, amask)
            if fault == "state_unchanged":
                return deltas, state._replace(
                    t_in_window=st.t_in_window,
                    sample_idx=st.sample_idx), m
            if fault == "answer_altered":
                m = m._replace(logits=m.logits + jnp.float32(0.05))
            return d, st, m

        chunk_fn.n_traces = inner.n_traces
        tier.chunk_fn = chunk_fn


def plant_train(step, fault: str):
    import jax
    import jax.numpy as jnp
    if fault not in TRAIN:
        raise ValueError(f"unknown training fault {fault!r}; have {TRAIN}")

    def faulty(params, state, events, labels):
        if fault == "bf16_weights":
            params = {**params, "hidden": {**params["hidden"],
                                           "w": _bf16(params["hidden"]["w"])}}
        if fault == "half_batch":
            h = events.shape[1] // 2

            def half(a, axis):
                return jax.lax.slice_in_dim(a, 0, h, axis=axis)

            sub = state._replace(
                layers=jax.tree_util.tree_map(lambda a: half(a, 1),
                                              state.layers),
                x_tr=half(state.x_tr, 0))
            p, st, m = step(params, sub, events[:, :h], labels[:h])

            def join(new, old, axis):
                rest = jax.lax.slice_in_dim(old, h, old.shape[axis],
                                            axis=axis)
                return jnp.concatenate([new, rest], axis=axis)

            st = st._replace(
                layers=jax.tree_util.tree_map(lambda a, b: join(a, b, 1),
                                              st.layers, state.layers),
                x_tr=join(st.x_tr, state.x_tr, 0))
            return p, st, m
        p, st, m = step(params, state, events, labels)
        if fault == "state_unchanged":
            return params, st, m
        return p, st, m._replace(local_loss=m.local_loss * 1.01)

    return faulty
