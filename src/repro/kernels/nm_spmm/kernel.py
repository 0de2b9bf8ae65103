"""Block-N:M sparse matmul Pallas kernel — ElfCore's forward path on the MXU.

TPU adaptation of the chip's input-stationary sparse datapath (Fig. 6):

* The dense contraction dimension K is split into ``bk``-row blocks; an N:M
  pattern keeps T = G·n blocks per ``bo``-wide output tile. Kept-block ids
  live in a small int32 table ``idx[J, T]`` that is **scalar-prefetched**
  (PrefetchScalarGridSpec) so the x-block ``index_map`` can gather the right
  activation block while the previous tile is still computing — Pallas'
  analogue of the chip streaming sparse indices one SRAM port ahead of the
  MACs.
* Grid = (rows, out-tiles, kept-blocks), kept-blocks innermost: the gathered
  x block and the compact weight block meet in VMEM, accumulate into an f32
  VMEM scratch tile, and the output is written once per (row, tile) — the
  input-stationary reuse that makes sparse *and* dense tiles the same MXU
  shape (128-aligned, no element-granular gather anywhere).
* Zero-skipping of the chip maps to *not iterating* pruned blocks at all:
  FLOPs and HBM traffic both scale with n/m.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(idx_ref, x_ref, w_ref, o_ref, acc_ref, *, n_kept: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # one MXU tile: gathered activation block @ compact weight block
    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32
    )

    @pl.when(t == n_kept - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def nm_spmm_pallas(
    x: jax.Array,          # [B, K]
    w_compact: jax.Array,  # [J, T, bk, bo]
    idx: jax.Array,        # [J, T] int32 global K-block ids
    *,
    bm: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, k = x.shape
    j, t, bk, bo = w_compact.shape
    assert b % bm == 0, (b, bm)
    assert k % bk == 0, (k, bk)

    grid = (b // bm, j, t)

    def x_map(i, jj, tt, idx_ref):
        return (i, idx_ref[jj, tt])

    def w_map(i, jj, tt, idx_ref):
        return (jj, tt, 0, 0)

    def o_map(i, jj, tt, idx_ref):
        return (i, jj)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((1, 1, bk, bo), w_map),
        ],
        out_specs=pl.BlockSpec((bm, bo), o_map),
        scratch_shapes=[pltpu.VMEM((bm, bo), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, n_kept=t),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, j * bo), x.dtype),
        # rows/tiles parallel; kept-block accumulation revisits the out tile
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx, x, w_compact)
