"""The gated three-factor sparse weight update (WU engine), per slot.

``dw[s, j, t] = scale[s] · pre[s, idx[j,t]] ⊗ mod[s, j·bo:(j+1)·bo]``

i.e. the outer-product update is computed **only for materialised blocks**,
on the compact layout — the chip never touches pruned synapses. ``scale``
folds the learning rate and the IA/SS gate (0 when gated off: the whole WU
is skipped, which is where the 52–65 % power cut comes from).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def wu_outer_slots(pre: jax.Array, mod: jax.Array, idx: jax.Array,
                   scale: jax.Array, bk: int, bo: int) -> jax.Array:
    """Per-slot compact outer-product update ``[S, J, T, bk, bo]``.

    Every slot keeps its own update — the serving per-stream delta rule.
    ``scale [S]`` carries the per-slot gate×lr.

    The multiply association mirrors the dense serving rule
    ``(scale · pre) · mod`` elementwise, so at every kept coordinate the
    update is **bitwise identical** to the dense-delta path's
    ``scale[:,None,None] * pre[:,:,None] * mod[:,None,:]``.
    """
    s, k = pre.shape
    j, t = idx.shape
    preb = pre.reshape(s, k // bk, bk)
    pg = preb[:, idx, :]                                    # [S, J, T, bk]
    modt = mod.reshape(s, j, bo)
    return ((scale[:, None, None, None] * pg)[..., None]
            * modt[:, :, None, None, :])
