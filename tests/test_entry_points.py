"""The entry points that run on the chip, exercised on the CPU: the
compile-cache helper, ``chip_smoke.py``'s refusal without a TPU, and
``repro.launch.serve`` at the reduced config."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parent.parent


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    try:
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == old  # not overridden
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_default_is_fixed_in_repo_path(monkeypatch):
    old = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        first = compile_cache.enable_compile_cache()
        assert first == compile_cache.enable_compile_cache()
        assert first == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def _run(args, tmp_path, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(REPO / "src"),
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    return subprocess.run([sys.executable, *args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_chip_smoke_refuses_cpu(tmp_path):
    proc = _run(["chip_smoke.py"], tmp_path, timeout=120)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_serve_reduced_answers_requests(tmp_path):
    """Two requests of the paper fleet, split into a mid-split group and
    a full-cloud group at this SLA."""
    proc = _run(["-m", "repro.launch.serve", "--reduced", "--requests", "2",
                 "--t-lim", "2.0"], tmp_path, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [line.split() for line in proc.stdout.splitlines()
            if line.startswith("dev")]
    assert [r[0] for r in rows] == ["dev0", "dev1"]
    assert sorted(int(r[2]) for r in rows) == [8, 10]
