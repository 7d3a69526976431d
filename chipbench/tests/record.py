#!/usr/bin/env python3
"""Record the traces the reader tests read, on a TPU.

    python chipbench/tests/record.py --out <dir> \
        --workload star2d_r2.sweep --workload star2d_r2.ensemble

Runs a short traced window of each cell named, cut by its path and the
rank of its grid (sweep: 2048² or 128x128x256, the same 2^22 points,
0.05 s of calls; served: 30 requests at 300/s from a pool of 4), and
writes ``<workload>.xplane.pb`` and ``<workload>.json`` into ``--out``:
the window's facts, the cut configuration and traffic, and ``read``,
what the cell's per-layer readers read from the window when it was
recorded (all but those of the set-up's clock).  ``--out`` may not lie
under ``chipbench/tests/data/``: a recording joins it as new files in
``data/spans/``, where ``test_reducers.py`` and ``test_spans.py`` find it
by the cell's name and pin it to its ``read``.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

#: the recorded sweep's grid by the configuration's rank
SWEEP_GRIDS = {2: [2048, 2048], 3: [128, 128, 256]}
SWEEP_SECONDS = 0.05
SERVE_RATE, SERVE_SECONDS = 300, 0.1
SERVE_CUT = {"pool": 4, "check_sample": 8}


def cut(cell) -> tuple[float, dict]:
    """Cuts ``cell`` to the recording's size; returns the window's length
    and keywords."""
    if cell.traffic["path"] == "serve":
        cell.traffic = dict(cell.traffic, **SERVE_CUT)
        return SERVE_SECONDS, {"rate": SERVE_RATE}
    cell.config = dict(cell.config,
                       grid=SWEEP_GRIDS[len(cell.config["grid"])])
    return SWEEP_SECONDS, {}


def record(cell, out: pathlib.Path, devices) -> dict:
    """One traced window of ``cell``, cut; returns the path's check."""
    import jax
    from chipbench import run, trace, work

    seconds, kw = cut(cell)
    counter = run.CompileCounter()
    path = cell.path.Path(cell.config, cell.traffic, 1, devices)
    path.setup()
    compiles, compile_s = counter.mark()
    xplane = out / f"{cell.name}.xplane.pb"
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=run.profile_options())
        try:
            win = path.window(seconds, **kw)
        finally:
            jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(d), xplane)
    readings = run.Readings(
        cell, win["facts"], trace.load(str(xplane), len(devices)),
        {"compile_s": compile_s, "compiles": compiles},
        work.peaks_for(devices[0].device_kind))
    source = {m["name"]: m["source"] for m in cell.bench["per_layer"]}
    read = {name: v["value"]
            for name, v in run.read_per_layer(cell, readings).items()
            if source[name] != "host_clock"}
    (out / f"{cell.name}.json").write_text(json.dumps(
        {"facts": win["facts"], "config": cell.config,
         "traffic": cell.traffic, "read": read}))
    path.release()
    return path.check()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", action="append", required=True,
                    help="a cell of BENCHMARK.json; may be repeated")
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out).resolve()
    if out.is_relative_to(DATA):
        print(f"record: --out {args.out} lies under {DATA}; record "
              f"elsewhere and add the files as new ones", file=sys.stderr)
        return 2
    out.mkdir(parents=True, exist_ok=True)
    # the compile cache of run.py, so that a run after this one is warm
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from chipbench import run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cells = [run.Cell(name) for name in args.workload]
    devices = run.chips(max(cell.chips for cell in cells))
    for cell in cells:
        print(cell.name, record(cell, out, devices[:cell.chips]),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
