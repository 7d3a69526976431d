"""Cells cut to a size the CPU runs in seconds, for the harness tests.

A cell is a name in ``BENCHMARK.json`` or an entry of a cell that is not
listed yet; either is cut by the rank of its grid.
"""
from chipbench import run
from repro.core.stencil_spec import PAPER_SUITE

#: the cut grid by the configuration's rank: 3-D runs in a few seconds
#: in interpret mode at 16x32x128, and keeps a full (8, 128) tile
SMALL_GRIDS = {2: [256, 256], 3: [16, 32, 128]}
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
#: a 3-D cell not listed yet (``box3d_cell``), held to a limit of its
#: own at CPU sizes only: between the program's largest reading, 4.5e-07,
#: and the control's smallest, 5.5e-06, over three seeds at the cut size.
#: Its entry names a configuration file that exists; ``box3d_cell``
#: replaces that configuration with the suite's box3d_r1.
BOX3D = {"name": "box3d_r1.sweep", "config": "star2d_r2", "traffic": "sweep",
         "chips": 1}
BOX3D_LIMITS = {"compared": {"rel_err": {"limit": 2e-06}}}


def listed(chips: int, path: str | None = None) -> list[str]:
    """The cells of BENCHMARK.json that take ``chips`` chips, on ``path``
    where it is given."""
    return [w["name"] for w in run.load_benchmark()["workloads"]
            if w["chips"] == chips and path in (
                None, run.load_json("traffic", w["traffic"])["path"])]


def suite_config(suite: str, grid) -> dict:
    """The configuration file of the suite's entry ``suite`` as a cell of
    its own would hold it: f32 contracted at HIGHEST, periodic, one chip."""
    spec = PAPER_SUITE()[suite]
    return {"suite": suite,
            "stencil": {"ndim": spec.ndim, "order": spec.order,
                        "shape": spec.shape,
                        "gather_coeffs": spec.gather_coeffs.tolist()},
            "dtype": "float32", "precision": "highest",
            "boundary": "periodic", "grid": list(grid), "mesh": None}


def small_cell(cell: str | dict, limits: dict | None = None) -> run.Cell:
    """``cell`` cut to a CPU size.  A name is looked up in BENCHMARK.json;
    an entry (``name``, ``config``, ``traffic``, ``chips``) is a cell not
    listed there, whose limits ``limits`` may give in place of its
    file."""
    if isinstance(cell, dict):
        bench = run.load_benchmark()
        bench = dict(bench, workloads=bench["workloads"] + [cell])
        out = run.Cell(cell["name"], bench)
    else:
        out = run.Cell(cell)
    if limits is not None:
        out.limits = limits
    out.config = cut(out.config)
    if out.traffic["path"] == "serve":
        out.traffic = dict(out.traffic, **SMALL_SERVE)
    return out


def cut(config: dict) -> dict:
    """``config`` with its grid cut by its rank."""
    return dict(config, grid=SMALL_GRIDS[len(config["grid"])])


def box3d_cell(limits: dict | None = None) -> run.Cell:
    """The suite's box3d_r1 on the sweep path in 8-step calls, cut: its
    configuration is built from ``PAPER_SUITE``, and it has no limits
    unless ``limits`` gives them."""
    cell = small_cell(BOX3D, limits)
    cell.config = cut(suite_config("box3d_r1", [512, 512, 512]))
    cell.traffic = dict(cell.traffic, steps_per_call=8)
    return cell
