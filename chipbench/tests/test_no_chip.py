"""A run without a TPU, or outside a checkout, exits non-zero and prints
no result."""
import os
import shutil
import subprocess
import sys

from chipbench import run


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "star2d_r2.sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(run.ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert "metrics" not in p.stdout and "{" not in p.stdout


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert "{" not in p.stdout
