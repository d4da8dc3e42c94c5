"""The command exits non-zero, before any set-up and with no result, where
JAX finds no TPU."""

import os
import subprocess
import sys

from chipbench import harness


def test_run_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"),
         "--workload", "tpch40-fit", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
