"""Public gated sparse-WU op: padding to the kernel's row tile, then the
kernel.

Whether the kernel runs at all is the caller's decision (the engine's
backend seam); ``interpret=True`` runs it in Pallas interpret mode, the
CPU test mode. The pure-jnp references — including the per-slot serving
update ``wu_outer_slots``, which has no kernel — live in ``ref.py``.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import wu_outer_pallas


def wu_outer(pre, mod, idx, scale, *, bk: int, bo: int,
             interpret: bool = False):
    """ΔW_compact = scale · gatherᵢ(pre) ⊗ mod, compact layout only."""
    scale = jnp.asarray(scale, pre.dtype)
    b = pre.shape[0]
    bb = min(128, b)
    pad = (-b) % bb
    if pad:
        pre = jnp.pad(pre, ((0, pad), (0, 0)))
        mod = jnp.pad(mod, ((0, pad), (0, 0)))
    return wu_outer_pallas(pre, mod, idx, scale, bk=bk, bo=bo, bb=bb,
                           interpret=interpret)
