"""Entry-point plumbing for chip runs: the compile-cache location, the
CPU-only scalability bench, and import-time side effects."""
import os
import subprocess
import sys

import jax
import pytest

from repro.compile_cache import ENV_VAR, use_compile_cache


@pytest.fixture
def restore_cache_config():
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])


def test_compile_cache_defaults_to_checkout(tmp_path, monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    path = use_compile_cache(str(tmp_path))
    assert path == os.path.join(str(tmp_path), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_environment(tmp_path, monkeypatch,
                                           restore_cache_config):
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "from-env"))
    before = jax.config.jax_compilation_cache_dir
    path = use_compile_cache(str(tmp_path))
    assert path == str(tmp_path / "from-env")
    # JAX reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_scalability_bench_refuses_tpu(monkeypatch):
    from benchmarks import bench_scalability

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU-only"):
        bench_scalability.main()


def test_scalability_bench_child_failure_is_fatal(monkeypatch):
    from benchmarks import bench_scalability

    def failed(*a, **k):
        return subprocess.CompletedProcess(a, 1, "", "boom")

    monkeypatch.setattr(bench_scalability.subprocess, "run", failed)
    with pytest.raises(RuntimeError, match="boom"):
        bench_scalability.main()


def test_dryrun_import_leaves_xla_flags_alone():
    code = (
        "import os; before = os.environ.get('XLA_FLAGS'); "
        "import repro.launch.dryrun; "
        "assert os.environ.get('XLA_FLAGS') == before, os.environ['XLA_FLAGS']"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
