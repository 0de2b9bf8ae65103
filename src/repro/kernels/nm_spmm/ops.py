"""Compact N:M layout builder: dense weights + unit mask -> the
``(w_compact, idx)`` pair that ``ref.nm_spmm`` and the serving path
consume (the chip's value/index SRAM pair)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_compact(w_dense: jax.Array, unit_mask: jax.Array, bk: int, bo: int,
                 n_kept: int | None = None):
    """Dense [K, O] + unit mask [K/bk, O/bo] -> (w_compact [J,T,bk,bo], idx [J,T]).

    Every out tile must keep the same *count* of blocks (N:M guarantees it).
    Pass ``n_kept`` (= G·n, known statically from the spec) when the mask is
    a tracer — e.g. building the compact carry inside a jitted step.
    """
    k, o = w_dense.shape
    kb, j = unit_mask.shape
    assert kb == k // bk and j == o // bo
    if n_kept is None:
        if isinstance(unit_mask, jax.core.Tracer):
            raise ValueError(
                "make_compact: unit_mask is a traced value, so the kept-block "
                "count cannot be read off it at trace time. Pass n_kept=... "
                "explicitly — it is static from the N:M spec "
                "(G·n, i.e. engine.compact_kept(cfg)).")
        t = int(unit_mask[:, 0].sum())
    else:
        t = n_kept
    idx = jnp.argsort(~unit_mask, axis=0, stable=True)[:t].T.astype(jnp.int32)  # [J, T]
    wb = w_dense.reshape(kb, bk, j, bo).transpose(2, 0, 1, 3)  # [J, KB, bk, bo]
    w_compact = jnp.take_along_axis(wb, idx[:, :, None, None], axis=1)
    return w_compact, idx
