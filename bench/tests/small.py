"""Small cells for the CPU tests: the benchmark's own cells with every
width cut down, driven through ``run.run_cell`` without the chip's checks
and without the persistent cache."""
from __future__ import annotations

import json
import time

from bench import harness
from bench.run import run_cell

SMALL_MODEL = {
    "n_in": 64, "n_hidden": 64, "n_out": 4, "t_steps": 16, "lanes": 8,
    "chunk_len": 5,
    "dsst": {"period": 8, "prune_frac": 0.25, "start_step": 0,
             "stop_step": 10 ** 9, "frac_decay": 1.0},
}
SMALL_TRAFFIC = {
    "compare_streams": 4, "pool_windows": 16, "cycle_windows": 64,
    "replacements": 2000, "batch": 8, "pool_batches": 4,
    "start_sample_idx": 5, "initial_session_windows": [1, 4],
    "session_windows": [2, 4],
}
SEED = 2 ** 31 + 11          # above 32 signed bits, as the driver's are


def small_cell(name: str) -> harness.Cell:
    cell = harness.find_cell(name)
    cell.config = {**cell.config, **{k: v for k, v in SMALL_MODEL.items()
                                     if k in cell.config}}
    cell.traffic = {**cell.traffic, **{k: v for k, v in SMALL_TRAFFIC.items()
                                       if k in cell.traffic}}
    return cell


def run_small(name: str, *, seconds: float = 0.6, trace: bool = False,
              fault: str = "", control: str = "", seed: int = SEED,
              cell=None):
    """The result line of one small run, as a dict."""
    cell = cell or small_cell(name)
    line, _ = run_cell(cell, seed=seed, seconds=seconds, trace=trace,
                       require_tpu=False, fault=fault, control=control,
                       t_start=time.perf_counter(), cache_dir=None)
    return json.loads(line)
