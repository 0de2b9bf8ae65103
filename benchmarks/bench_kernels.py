"""Per-kernel structural benchmarks: HBM traffic of the flash-attention
kernel from its BlockSpec schedule (the TPU-honest numbers; wall-clock of
interpret mode is meaningless). Correctness itself is pytest's job."""
from __future__ import annotations

from repro.kernels.flash_attn.ops import hbm_bytes, xla_score_path_bytes


def run(quick: bool = True):
    rows = []

    # flash attention: BlockSpec-exact traffic vs unfused score path at the
    # deepseek train cell's per-device slice
    fl = hbm_bytes(16, 4096, 4, 128)
    xla = xla_score_path_bytes(16, 4096, 4, 128)
    rows.append({"name": "kernels/flash_attn_traffic_4k", "us_per_call": 0.0,
                 "derived": (f"flash_B={fl:.3e};score_path_B={xla:.3e};"
                             f"cut={1 - fl/xla:.2f}")})
    fl32 = hbm_bytes(2, 32768, 4, 128)
    xla32 = xla_score_path_bytes(2, 32768, 4, 128)
    rows.append({"name": "kernels/flash_attn_traffic_32k", "us_per_call": 0.0,
                 "derived": (f"flash_B={fl32:.3e};score_path_B={xla32:.3e};"
                             f"cut={1 - fl32/xla32:.2f}")})
    return rows
