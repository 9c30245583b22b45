"""The command refuses to run where it cannot measure."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("workload", ["w22a.serve-poisson", "no.such-cell"])
def test_command_exits_nonzero_and_prints_no_result_without_a_chip(
        workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", str(2**35 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert '"correct"' not in done.stdout
    if workload == "w22a.serve-poisson":
        assert done.returncode == 2
        assert "cpu" in done.stderr
