"""Public fused-LIF op: padding to the kernel's tiles, then the kernel.

Whether the kernel runs at all is the caller's decision (the engine's
backend seam); ``interpret=True`` runs it in Pallas interpret mode, the
CPU test mode. The pure-jnp reference is ``ref.lif_step``.
"""
from __future__ import annotations

import jax.numpy as jnp

from .kernel import lif_pallas


def _pad_to(x, bm, bn):
    b, n = x.shape
    pb, pn = (-b) % bm, (-n) % bn
    if pb or pn:
        x = jnp.pad(x, ((0, pb), (0, pn)))
    return x


def lif_step(v, tr, current, *, alpha: float, beta: float, theta: float,
             interpret: bool = False):
    """Fused LIF update through the Pallas kernel, any ``[B, N]`` shape."""
    b, n = v.shape
    bm, bn = 8, 128
    vp, trp, ip = (_pad_to(a, bm, bn) for a in (v, tr, current))
    vo, tro, s = lif_pallas(vp, trp, ip, alpha=alpha, beta=beta, theta=theta,
                            bm=bm, bn=bn, interpret=interpret)
    return vo[:b, :n], tro[:b, :n], s[:b, :n]
