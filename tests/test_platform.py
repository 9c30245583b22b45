"""The platform picks the kernel path; the CLIs' compile cache; the
benchmark's child processes stay off an accelerator."""

from __future__ import annotations

import os
import subprocess
import sys
import uuid
from pathlib import Path

import jax
import pytest

from repro.core.trainer import SNNTrainConfig
from repro.engine import SNNEnginePlan

REPO = Path(__file__).resolve().parents[1]


def _env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = (str(REPO / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


def test_default_kernel_backend_is_ref_off_tpu():
    assert jax.default_backend() != "tpu"
    assert SNNEnginePlan().kernel_backend == "ref"
    assert SNNTrainConfig().plan().kernel_backend == "ref"
    assert SNNEnginePlan(kernel_backend="interp").kernel_backend == "interp"


def test_default_kernel_backend_is_tpu_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert SNNEnginePlan().kernel_backend == "tpu"
    assert SNNTrainConfig().plan().kernel_backend == "tpu"
    assert SNNEnginePlan(kernel_backend="ref").kernel_backend == "ref"


def test_compile_cache_stays_off_on_the_cpu():
    from repro.launch.compile_cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


# The helper sees a TPU platform, picks the directory, then a CPU compile
# shows where JAX writes its entries.
_PROBE = """
import jax, numpy as np
from repro.launch import compile_cache
cpu = jax.default_backend
jax.default_backend = lambda: "tpu"
print(compile_cache.enable_compile_cache())
jax.default_backend = cpu
def {name}(x):
    return x * 3 + 1
print(np.asarray(jax.jit({name})(np.arange(5))).sum())
"""


def _run_probe(env) -> tuple[str, str]:
    name = f"cache_probe_{uuid.uuid4().hex[:12]}"
    proc = subprocess.run([sys.executable, "-c", _PROBE.format(name=name)],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr
    return proc.stdout.split()[0], name


def _entries(cache: Path, name: str) -> list[Path]:
    if not cache.exists():
        return []
    return [f for f in cache.iterdir() if f.name.startswith(f"jit_{name}-")]


def test_compile_cache_stays_in_the_env_directory(tmp_path):
    cache = tmp_path / "cache"
    got, name = _run_probe(_env(JAX_COMPILATION_CACHE_DIR=str(cache)))
    assert got == str(cache)
    assert _entries(cache, name)
    assert not _entries(REPO / ".jax_cache", name)


def test_compile_cache_defaults_to_the_checkout():
    got, name = _run_probe(_env())
    assert got == str(REPO / ".jax_cache")
    entries = _entries(REPO / ".jax_cache", name)
    assert entries
    for f in entries:
        f.unlink()


def test_mesh_bench_rows_refuse_to_fork_off_cpu(monkeypatch):
    if str(REPO) not in sys.path:
        monkeypatch.syspath_prepend(str(REPO))
    from benchmarks import kernels_bench

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="only runs on the CPU"):
        kernels_bench._mesh_bench(["--devices", "8"], "BENCH")
