"""Compute ops of ElfCore's hot spots, and the LM path's Pallas kernel.

The SNN engine (``core/engine.py``) runs jnp ops, lowered by XLA for the
device that runs them:

* :mod:`repro.kernels.nm_spmm`  — block-N:M sparse matmul on the compact
  layout (``ops.make_compact`` builds it; ``ref.nm_spmm`` is the base
  current, ``ref.nm_spmm_deltas`` the per-slot delta current).
* :mod:`repro.kernels.wu_outer` — gated three-factor sparse weight update
  per slot on the compact layout (the WU engine of Fig. 2).

No Pallas kernel is on the SNN path. One returns only when it wins a
benchmark cell, chosen from the N:M spec, never from a user option.

* :mod:`repro.kernels.flash_attn` — Pallas flash attention for the LM
  stack (``kernel.py`` + ``ops.py`` wrapper + ``ref.py`` oracle).
"""
