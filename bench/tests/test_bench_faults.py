"""The whole run, driven on the CPU at a small size with the chip's look
skipped: sound runs come out correct, and the control (the reference at
the precision below the configuration's, in the program's place) and each
fault planted under the timed path (``bench/faults.py``) make ``correct``
false."""
from __future__ import annotations

import pytest

from bench import faults
from bench.tests.small import run_small, small_cell

# Training's control differs from the reference only where float32 at
# ``high`` flips a spike, so its test needs a step that makes enough spike
# decisions: the full T and a larger layer and batch than the other tests.
CONTROL_TRAIN = {"n_in": 256, "n_hidden": 256, "t_steps": 50}


@pytest.mark.parametrize("name", ["serve_steady", "serve_churn",
                                  "train_dsst"])
def test_sound_run_is_correct(name):
    out = run_small(name)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert all(set(v) == {"value", "limit"} for v in out["checks"].values())
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("name", ["serve_steady", "serve_churn",
                                  "train_dsst"])
def test_control_is_not_correct(name):
    cell = small_cell(name)
    if name == "train_dsst":
        cell.config = {**cell.config, **CONTROL_TRAIN}
        cell.traffic = {**cell.traffic, "batch": 128}
    out = run_small(name, control="high", cell=cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", faults.SERVE)
def test_serving_fault_is_caught(fault):
    out = run_small("serve_steady", fault=fault)
    assert not out["correct"], out["checks"]


def test_churn_fault_is_caught():
    out = run_small("serve_churn", fault="answer_altered")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", faults.TRAIN)
def test_training_fault_is_caught(fault):
    out = run_small("train_dsst", fault=fault)
    assert not out["correct"], out["checks"]
