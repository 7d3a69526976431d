"""Every cell, configuration, traffic mix, limit and metric is found by
the name BENCHMARK.json gives it."""
import json

import numpy as np
import pytest

from chipbench import run

BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = run.Cell(name)
    assert cell.config and cell.traffic and cell.limits["compared"]
    assert hasattr(cell.path, "Path")
    e2e = [m["name"] for m in run.cell_metrics(BENCH, name, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert run.cell_metrics(BENCH, name, "per_layer")


@pytest.mark.parametrize("m", [m["name"] for m in BENCH["per_layer"]])
def test_metric_reader_found_by_name(m):
    assert callable(run.load_module("metrics", m).read)


def test_config_files_are_unique_and_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        assert json.loads((run.ROOT / c["file"]).read_text())["source"]


def test_config_coefficients_are_the_papers_suite():
    from repro.core.stencil_spec import PAPER_SUITE
    want = PAPER_SUITE()["star2d_r2"].gather_coeffs
    for c in BENCH["configs"]:
        got = json.loads((run.ROOT / c["file"]).read_text())
        np.testing.assert_array_equal(
            np.asarray(got["stencil"]["gather_coeffs"]), want)


def test_unknown_names_fail():
    with pytest.raises(run.BenchError):
        run.Cell("no_such.cell")
    with pytest.raises(run.BenchError):
        run.load_module("metrics", "no_such_metric")
