"""A run without a TPU, outside a checkout, or of a cell that has no
limit file yet exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

from chipbench import run


def _run(cwd, env_extra, workload="star2d_r2.sweep"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", workload,
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


def test_listed_cell_without_limits_is_refused(tmp_path):
    # a checkout whose BENCHMARK.json lists a cell before its limits are
    # measured: the run stops before it looks for the chip
    bench = run.load_benchmark()
    bench["workloads"].append({"name": "star2d_r2.unmeasured",
                               "config": "star2d_r2", "traffic": "sweep",
                               "chips": 1})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    shutil.copytree(run.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (tmp_path / "src").symlink_to(run.ROOT / "src")
    p = _run(tmp_path, {}, "star2d_r2.unmeasured")
    assert p.returncode == 2
    assert "limits/star2d_r2.unmeasured.json" in p.stderr
    assert "no TPU" not in p.stderr and "{" not in p.stdout
