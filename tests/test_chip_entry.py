"""What a CPU run can check about the chip entry points: where the compile
cache is placed, and that chip_smoke.py / bench.py refuse to run off-TPU."""

import os
import subprocess
import sys

import jax

from ytklearn_tpu.compile_cache import configure_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_set_code_sets_nothing(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    monkeypatch.chdir(tmp_path)
    assert configure_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_unset_is_checkout_regardless_of_cwd(tmp_path):
    """In a child, so the setting does not leak into this test process."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = REPO
    code = (
        "import jax; from ytklearn_tpu.compile_cache import configure_compile_cache as c;"
        "print(c()); print(jax.config.jax_compilation_cache_dir)"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env=env, cwd=tmp_path, timeout=300,
    )
    assert r.returncode == 0, r.stderr
    want = os.path.join(REPO, ".jax_cache")
    assert r.stdout.split() == [want, want]


def _run_off_tpu(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script)],
        capture_output=True, text=True, env=env, timeout=300,
    )


def test_chip_smoke_refuses_cpu():
    r = _run_off_tpu("chip_smoke.py")
    assert r.returncode not in (0, None)
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout  # no result line


def test_bench_refuses_cpu():
    r = _run_off_tpu("bench.py")
    assert r.returncode not in (0, None)
    assert "no TPU found" in r.stderr
    assert '"metric"' not in r.stdout
