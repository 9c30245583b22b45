"""``chip_smoke.py`` on the CPU: it refuses to run without a TPU, and
its phases, rehearsed in Pallas interpret mode, hold their checks (the
chip run then only has to find what the chip itself changes)."""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
    import chip_smoke
    return chip_smoke


@pytest.fixture
def interp_default(monkeypatch):
    """Plans that follow the platform pick interpret mode here, where
    on the chip they pick the compiled kernels."""
    from repro.engine import plan

    monkeypatch.setattr(plan, "default_kernel_backend", lambda: "interp")


def _env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_chip_smoke_refuses_the_cpu():
    proc = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                          env=_env(), capture_output=True, text=True,
                          timeout=300, cwd=REPO)
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert '"ok"' not in proc.stdout
    assert "no CPU fallback" in proc.stderr


def test_chip_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in _env().items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_window_ops_phase_in_interpret_mode(smoke):
    smoke.phase_kernels(0x22A, backend="interp")


def test_serve_phase_in_interpret_mode(smoke, interp_default):
    smoke.phase_serve(0x22A, n_req=64, backend="interp")


def test_train_phase_in_interpret_mode(smoke, interp_default):
    smoke.phase_train(0x22A, n_samples=16, backend="interp")


def test_mesh_phase_on_four_virtual_devices():
    code = ("import chip_smoke; "
            "chip_smoke.phase_mesh(0x22A, n=256, b=8, backend='interp')")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.count("equal_to_one_chip=True") == 8
    assert "infer_devices=4" in proc.stdout
