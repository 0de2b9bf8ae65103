"""Public op: block-N:M sparse matmul with sparse-to-sparse gradients.

Everything here runs the Pallas kernel (``kernel.py``); ``interpret=True``
runs it in Pallas interpret mode, the CPU test mode. Whether the kernel is
used at all is decided by the caller — the engine's backend seam
(``core.engine.make_backend``) — never by the platform; the pure-jnp
reference lives in ``ref.py``. ``nm_spmm(x, w_compact, idx)`` wraps the
kernel in a ``custom_vjp`` whose backward pass **never materialises the
dense weight matrix**:

* ``dx``        — transposed sparse matmul, assembled block-wise;
* ``dw_compact``— gradient *only for materialised blocks* (gather x blocks,
  per-block outer product). This is the chip's sparse WU philosophy: pruned
  connections receive no gradient storage; DSST's regrow scoring instead
  uses the factorized |pre|·|post| statistics (core/dsst.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import nm_spmm_pallas


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def nm_spmm(x, w_compact, idx, interpret=False):
    return nm_spmm_batched(x, w_compact, idx, interpret=interpret)


def _fwd(x, w_compact, idx, interpret):
    return (nm_spmm_batched(x, w_compact, idx, interpret=interpret),
            (x, w_compact, idx))


def _bwd(interpret, res, dy):
    x, w_compact, idx = res
    j, t, bk, bo = w_compact.shape
    b, k = x.shape
    dyt = dy.reshape(b, j, bo)

    # dx: scatter-add transposed block matmuls into the kept rows only.
    dxg = jnp.einsum("bjo,jtko->bjtk", dyt, w_compact)      # [B, J, T, bk]
    dxb = jnp.zeros((b, k // bk, bk), x.dtype)
    dxb = dxb.at[:, idx, :].add(dxg.astype(x.dtype))
    dx = dxb.reshape(b, k)

    # dw_compact: gradient only where a block is materialised.
    xb = x.reshape(b, k // bk, bk)
    xg = xb[:, idx, :]                                      # [B, J, T, bk]
    dwc = jnp.einsum("bjtk,bjo->jtko", xg, dyt).astype(w_compact.dtype)
    return dx, dwc, None


nm_spmm.defvjp(_fwd, _bwd)


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


def nm_spmm_batched(x, w_compact, idx, *, interpret: bool = False):
    """Row-count-agnostic kernel forward (no custom VJP): pads the row
    dimension to the kernel's ``bm`` tile. The engine's local learning
    rules never backprop through the forward matmul, so its hot path calls
    this directly instead of :func:`nm_spmm`.
    """
    b = x.shape[0]
    bm = 128 if b >= 128 else 8
    pad = (-b) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    y = nm_spmm_pallas(x, w_compact, idx, bm=bm, interpret=interpret)
    return y[:b]
