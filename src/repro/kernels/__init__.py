"""Pallas TPU kernels for ElfCore's compute hot-spots.

Each kernel ships as a triple (DESIGN.md §7):

* ``<name>/kernel.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling,
  written for TPU (MXU-aligned tiles, scalar-prefetched index tables) and
  validated in ``interpret=True`` mode on CPU;
* ``<name>/ops.py``    — the public wrapper (padding, custom_vjp) around the
  kernel, with an ``interpret`` flag; whether a kernel runs at all is
  decided by the engine's backend seam (``core.engine.make_backend``);
* ``<name>/ref.py``    — the pure-jnp oracle the tests sweep against.

Kernels:

* :mod:`repro.kernels.nm_spmm`  — block-N:M sparse matmul (input-stationary
  forward path of Fig. 6, adapted from the chip's 4 parallel PEs to MXU
  tiles gathered by a scalar-prefetched block-index table).
* :mod:`repro.kernels.lif`      — fused LIF membrane + threshold/reset +
  trace decay (one HBM round-trip for the whole neuron update).
* :mod:`repro.kernels.wu_outer` — gated three-factor sparse weight update on
  the compact N:M layout (the WU engine of Fig. 2).
"""
