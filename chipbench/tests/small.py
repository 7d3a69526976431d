"""Cells cut to a size the CPU runs in seconds, for the harness tests."""
from chipbench import run

SMALL_GRID = [256, 256]
SMALL_SERVE = dict(grids=[{"grid": [64, 64], "share": 0.75},
                          {"grid": [128, 128], "share": 0.25}],
                   clients=4, pool=4, check_sample=16, max_batch=4)
#: the (2,2) mesh cell: the sweep path on the ``star2d_r2_49k``
#: configuration.  It is not in BENCHMARK.json and has no limit file until
#: it has been measured on four chips (PERF.md, Open questions), so these
#: tests hold it to a limit of their own, at CPU sizes only.
MESH = {"name": "star2d_r2_49k.mesh", "config": "star2d_r2_49k",
        "traffic": "sweep", "chips": 4}
MESH_LIMITS = {"compared": {"rel_err": {"limit": 5e-06}}}


def small_cell(name: str) -> run.Cell:
    if name == MESH["name"]:
        cell = run.Cell("star2d_r2.sweep")
        cell.entry, cell.name, cell.chips = MESH, MESH["name"], MESH["chips"]
        cell.config = run.load_json("configs", MESH["config"])
        cell.limits = MESH_LIMITS
    else:
        cell = run.Cell(name)
    cell.config = dict(cell.config, grid=SMALL_GRID)
    if cell.traffic["path"] == "serve":
        cell.traffic = dict(cell.traffic, **SMALL_SERVE)
    return cell
