"""The chip benchmark's files, checked with no device work in the tier-1
run: the lookup checks of the harness's own tests
(``chipbench/tests/test_lookup.py``), run here too.  Every cell of
``BENCHMARK.json`` resolves by name to its configuration, traffic, limits
and path and reports ``setup_s`` and another end-to-end metric, every
per-layer reader loads, every configuration is the entry of the paper's
suite it names, and every ``workloads`` list names cells that exist.  A
cell added with a file missing or misnamed fails here, before any chip
run.
"""
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import run  # noqa: E402
from chipbench.tests import test_lookup as lookup  # noqa: E402


@pytest.mark.parametrize("name", lookup.CELLS)
def test_cell_resolves_by_name(name):
    lookup.test_cell_found_by_name(name)
    cell = run.Cell(name)
    assert cell.chips in (1, 4)
    if cell.chips > 1:
        assert int(np.prod(cell.config["mesh"]["shape"])) == cell.chips


@pytest.mark.parametrize("name",
                         [m["name"] for m in lookup.BENCH["per_layer"]])
def test_reader_loads(name):
    lookup.test_metric_reader_found_by_name(name)


@pytest.mark.parametrize("file", lookup.CONFIG_FILES)
def test_config_is_its_suite_entry(file):
    lookup.test_config_coefficients_are_the_papers_suite(file)


def test_workloads_name_cells_that_exist():
    lookup.test_workloads_lists_name_cells_that_exist()


def test_configurations_differ_in_source_or_reduced():
    """Two entries that cite the same document and list the same
    ``reduced`` keys are one configuration: a deployment of its own
    names the keys it sets apart from its source."""
    seen = {}
    for c in lookup.BENCH["configs"]:
        key = (c["source"].split()[0], tuple(sorted(c["reduced"])))
        assert key not in seen, (c["name"], seen.get(key))
        seen[key] = c["name"]


@pytest.mark.parametrize("name", [c["name"] for c in lookup.BENCH["configs"]])
def test_reduced_names_keys_of_the_configuration(name):
    c = run.find(lookup.BENCH["configs"], name, "config")
    config = run.load_json("configs", name)
    assert len(set(c["reduced"])) == len(c["reduced"]) <= 16
    assert set(c["reduced"]) <= set(config), c["reduced"]
