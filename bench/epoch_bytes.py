"""Bytes a live DSST epoch moves on each chip, besides its delta shard.

The epoch program reads the shared base and writes the new one on every
chip (the base is replicated): the dense weights and mask and the readout,
read and written, and the serving exec rep (kept values and their input
ids) written. The delta shard's bytes, read and written by the
projection, come from the program itself (the ``bytes_projected``
attribute of its ``topology.epoch`` span).
"""
from __future__ import annotations

from bench.reference.snn import nm_counts


def base_bytes(cfg) -> int:
    """Base bytes read plus written by one epoch on one chip (f32 weights,
    one byte a mask entry, int32 ids)."""
    L, K, N, O = cfg["n_layers"], cfg["n_in"], cfg["n_hidden"], cfg["n_out"]
    m, n = nm_counts(cfg)
    kept = L * N * (K // m) * n              # the compact rep's entries
    dense = L * K * N * 4 + L * K * N + L * N * O * 4
    return 2 * dense + kept * (4 + 4)
