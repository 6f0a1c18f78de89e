"""Without a TPU a run exits non-zero and prints no result, also from a
directory that holds only BENCHMARK.json and the benchmark's files."""
import os
import shutil
import subprocess
import sys

from conftest import CHIP, ROOT


def _run(root, workload="citeseer.motifs3"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "chip", "run.py"),
         "--workload", workload, "--seed", "2147483653", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_workload_no_result():
    p = _run(ROOT, "no-such.cell")
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_bare_directory_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
