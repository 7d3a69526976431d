"""Every cell, configuration, traffic mix, limit and metric is found by
the name BENCHMARK.json gives it, and every configuration is the entry of
the paper's suite that it names.  A cell added with its files but listed
wrongly, or a configuration that departs from its suite entry, fails
here before any run."""
import json

import numpy as np
import pytest

from chipbench import run

BENCH = run.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIG_FILES = sorted(p.name for p in (run.HERE / "configs").glob("*.json"))


@pytest.mark.parametrize("name", CELLS)
def test_cell_found_by_name(name):
    cell = run.Cell(name)
    assert cell.config and cell.traffic
    assert cell.limits is not None and cell.limits["compared"]
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
        # the harness finds a configuration by its name, not by ``file``
        assert c["file"] == f"{BENCH['paths'][0]}/configs/{c['name']}.json"
        assert json.loads((run.ROOT / c["file"]).read_text())["source"]


def test_workloads_lists_name_cells_that_exist():
    configs = {c["name"] for c in BENCH["configs"]}
    assert {w["config"] for w in BENCH["workloads"]} == configs
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= set(CELLS), m["name"]


@pytest.mark.parametrize("like", CELLS)
def test_a_cell_listed_under_what_it_reports_gets_its_per_layer_metrics(
        like):
    """A new cell appended to the ``workloads`` of the end-to-end metrics
    a listed cell reports gets the per-layer metrics that cell gets."""
    new = dict(run.find(BENCH["workloads"], like, "workload"), name="new.x")
    e2e = {m["name"] for m in run.cell_metrics(BENCH, like, "end_to_end")}
    bench = dict(BENCH, workloads=BENCH["workloads"] + [new], end_to_end=[
        dict(m, workloads=m["workloads"] + ["new.x"])
        if m["name"] in e2e and "workloads" in m else m
        for m in BENCH["end_to_end"]])
    for kind in ("end_to_end", "per_layer"):
        assert run.cell_metrics(bench, "new.x", kind) == run.cell_metrics(
            bench, like, kind)


@pytest.mark.parametrize("file", CONFIG_FILES)
def test_config_coefficients_are_the_papers_suite(file):
    from repro.core.stencil_spec import PAPER_SUITE
    got = run.load_json("configs", file[:-len(".json")])
    want = PAPER_SUITE()[got["suite"]]
    st = got["stencil"]
    np.testing.assert_array_equal(np.asarray(st["gather_coeffs"]),
                                  want.gather_coeffs)
    assert (st["ndim"], st["order"], st["shape"]) == (
        want.ndim, want.order, want.shape)
    assert len(got["grid"]) == want.ndim


def test_unknown_names_fail():
    with pytest.raises(run.BenchError):
        run.Cell("no_such.cell")
    with pytest.raises(run.BenchError):
        run.load_module("metrics", "no_such_metric")
