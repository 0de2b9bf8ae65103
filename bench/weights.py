"""Seeded random weights of the ElfCore network, made by the benchmark.

The benchmark, not the program, makes the weights, so that the reference
may start from the very same bits. One jitted call on the default device
builds every leaf in the layout the program consumes:
``{"hidden": {"w" f32[L, K, N], "mask" bool[L, K, N]}, "readout"
f32[L, N, n_out]}`` — element-granular N:M (``n`` of every ``m = K/4``
consecutive inputs kept per output column), weights zero off the mask.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.snn import nm_counts


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (also above 2**32:
    the high word is folded in, so no two seeds share a key)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_params(cfg, seed: int):
    """Weights for ``cfg`` (the configuration dict) from ``seed``."""
    L, K, N, O = cfg["n_layers"], cfg["n_in"], cfg["n_hidden"], cfg["n_out"]
    if cfg["n_in"] != cfg["n_hidden"]:
        raise ValueError("the stacked layout needs n_in == n_hidden")
    m, n = nm_counts(cfg)

    @jax.jit
    def build(key):
        kw, km, kr = jax.random.split(key, 3)
        scores = jax.random.uniform(km, (L, K // m, m, N))
        # rank by a double argsort: exactly n kept per group, ties or not
        rank = jnp.argsort(jnp.argsort(scores, axis=2), axis=2)
        mask = (rank < n).reshape(L, K, N)
        w = jax.random.normal(kw, (L, K, N)) * (1.5 / jnp.sqrt(K * n / m))
        readout = jax.random.normal(kr, (L, N, O)) * 0.05
        return {"hidden": {"w": jnp.where(mask, w, 0.0), "mask": mask},
                "readout": readout}

    return build(seed_key(seed))
