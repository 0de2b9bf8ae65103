"""Pure-jnp oracle for the block-N:M sparse matmul.

Layouts (shared with kernel.py / ops.py):

* ``x``         : [B, K] activations (B = flattened batch·seq rows).
* ``w_compact`` : [J, T, bk, bo] — for each of J output tiles (bo columns),
                  the T = G·n kept K-blocks of bk rows each.
* ``idx``       : [J, T] int32 — *global* K-block index of each kept block
                  (row block ``idx[j, t]`` spans x[:, idx*bk : (idx+1)*bk]).

``y[:, j·bo:(j+1)·bo] = Σ_t x[:, idx[j,t]] @ w_compact[j, t]``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def densify(w_compact: jax.Array, idx: jax.Array, k: int) -> jax.Array:
    """Compact [J, T, bk, bo] + idx [J, T] -> dense [K, O] with zeros."""
    j, t, bk, bo = w_compact.shape
    dense = jnp.zeros((k // bk, bk, j, bo), w_compact.dtype)
    for_j = jnp.repeat(jnp.arange(j), t)
    for_t = jnp.tile(jnp.arange(t), j)
    blocks = w_compact[for_j, for_t]                       # [J*T, bk, bo]
    dense = dense.at[idx[for_j, for_t], :, for_j, :].add(blocks)
    return dense.reshape(k, j * bo)


def nm_spmm(x: jax.Array, w_compact: jax.Array, idx: jax.Array) -> jax.Array:
    """Reference forward: gather x blocks, per-tile dense matmul."""
    j, t, bk, bo = w_compact.shape
    b, k = x.shape
    xb = x.reshape(b, k // bk, bk)
    xg = xb[:, idx, :]                                     # [B, J, T, bk]
    y = jnp.einsum("bjtk,jtko->bjo", xg, w_compact)
    return y.reshape(b, j * bo)


def nm_spmm_deltas(x, delta_compact, idx):
    """Per-slot compact delta matmul: ``y[s] = x[s] @ densify(delta[s])``.

    ``x [S, K]`` with per-slot compact deltas ``[S, J, T, bk, bo]`` sharing
    one ``idx [J, T]`` (every stream lives on the fleet's topology). The
    gather mirrors :func:`nm_spmm`; only the batch axis rides along on the
    weight operand. Used by the serving hot path so the per-stream delta
    current never round-trips through a dense ``[K, N]`` tensor.
    """
    s, k = x.shape
    _, j, t, bk, bo = delta_compact.shape
    xb = x.reshape(s, k // bk, bk)
    xg = xb[:, idx, :]                                      # [S, J, T, bk]
    y = jnp.einsum("sjtk,sjtko->sjo", xg, delta_compact)
    return y.reshape(s, j * bo)


def nm_spmm_dense_ref(x: jax.Array, w_compact: jax.Array, idx: jax.Array) -> jax.Array:
    """Second, independent oracle via densify (used in tests)."""
    k = x.shape[1]
    return x @ densify(w_compact, idx, k)
