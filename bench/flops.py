"""Model FLOPs of the ElfCore network, from the configuration's shapes.

Counted per stream-timestep (serving) or per sample-timestep (training),
the same whatever implements the step:

* forward: 2 x kept connections of every hidden layer, plus 2 x the
  bypass readout (``n_hidden x n_out`` per hidden layer);
* three-factor weight update: 2 x kept connections of every hidden layer,
  on the timesteps where updates run (``t >= wu_start_frac * T``),
  counted whether or not the activity gate opens — so the gate's savings
  show as a higher rate, not as fewer FLOPs.

Element-wise neuron work (LIF, traces, gate, modulator) is left out: it is
O(n_hidden) against O(kept connections).
"""
from __future__ import annotations

from bench.reference.snn import nm_counts


def kept_per_layer(cfg) -> int:
    m, n = nm_counts(cfg)
    return (cfg["n_in"] // m) * n * cfg["n_hidden"]


def per_timestep(cfg) -> float:
    """Mean model FLOPs of one stream- or sample-timestep."""
    L = cfg["n_layers"]
    fwd = 2 * L * (kept_per_layer(cfg) + cfg["n_hidden"] * cfg["n_out"])
    T = cfg["t_steps"]
    wu_steps = T - int(T * cfg["wu_start_frac"])
    wu = 2 * L * kept_per_layer(cfg) * wu_steps / T
    return float(fwd + wu)
