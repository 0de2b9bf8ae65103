"""``BENCHMARK.json`` and the files it names: each cell's configuration,
traffic and metrics are found by name, and the command refuses to run
without a TPU."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness, peaks

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def man():
    return harness.load_manifest()


def test_manifest_keys_and_names(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert man["paths"] == ["bench"]
    assert man["command"] == ["python3", "bench/run.py"]
    names = [c["name"] for c in man["configs"]] + \
        [w["name"] for w in man["workloads"]] + \
        [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for e in man["configs"] + man["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"], e
    for m in man["end_to_end"] + man["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    e2e = {m["name"] for m in man["end_to_end"]}
    for m in man["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("cell", ["serve_steady", "train_dsst",
                                  "serve_churn"])
def test_cell_files_are_found_by_name(cell):
    c = harness.find_cell(cell)
    assert c.chips == 1
    assert harness.mode_runner(c) is not None
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    for key in c.config["reduced"]:
        assert key in c.config


def test_configs_match_manifest(man):
    for conf in man["configs"]:
        with open(os.path.join(harness.ROOT, conf["file"])) as f:
            data = json.load(f)
        assert data["name"] == conf["name"]
        assert data["reduced"] == conf["reduced"]
        assert data["source"] == conf["source"]
        assert conf["file"].startswith("bench/")


def test_unknown_device_kind_has_no_peaks():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peak("cpu")


def test_command_refuses_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"),
         "--workload", "serve_steady", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
