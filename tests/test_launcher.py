"""Fleet launcher: validate gate + local smoke train (single host)."""
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, extra_env=None, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    return subprocess.run([sys.executable, "-m", "repro.launch.launcher"] + args,
                          capture_output=True, text=True, env=env,
                          timeout=timeout)


def test_validate_gate_production_mesh():
    """--validate lowers the full-scale arch on the 512-dev mesh (CI gate)."""
    out = _run(["--arch", "qwen2_vl_2b", "--validate", "--multi-pod"],
               extra_env={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=512"})
    assert out.returncode == 0, out.stdout + out.stderr
    assert "validate OK" in out.stdout


def test_local_smoke_train_falls_back():
    """Without 512 devices the launcher reduces the config and trains."""
    out = _run(["--arch", "stablelm_12b", "--steps", "4",
                "--seq-len", "32", "--global-batch", "4",
                "--opt", "zero1"])
    assert out.returncode == 0, out.stdout + out.stderr
    assert "loss" in out.stdout
