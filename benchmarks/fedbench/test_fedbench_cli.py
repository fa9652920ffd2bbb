"""The command refuses to run without a TPU and prints no result."""
import os
import subprocess
import sys

from fedbench import spec


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/fedbench/run.py", "--workload",
         "cnn_fedbwo_paper", "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=spec.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert "metrics" not in proc.stdout and proc.stdout.strip() == ""
