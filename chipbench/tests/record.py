#!/usr/bin/env python3
"""Record the traces the reader tests read, on a TPU.

    python chipbench/tests/record.py --out <dir>

Runs a short traced window of each cell at a cut size (sweep: 2048²,
0.05 s of 16-step calls; served: 30 requests at 300/s from a pool of 4)
and writes ``<workload>.xplane.pb`` and ``<workload>.json`` (the window's
facts and the cut configuration and traffic) into ``--out``; copy them
into ``chipbench/tests/data/spans/`` and re-pin ``test_spans.py`` to what
they read.
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
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

SWEEP_GRID = [2048, 2048]
SWEEP_SECONDS = 0.05
SERVE_RATE, SERVE_SECONDS = 300, 0.1
SERVE_CUT = {"pool": 4, "check_sample": 8}


def record(cell, seconds: float, out: pathlib.Path, devices,
           rate=None) -> dict:
    """One traced window of ``cell``; returns the path's check."""
    import jax
    from chipbench import run, trace

    path = cell.path.Path(cell.config, cell.traffic, 1, devices)
    path.setup()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=run.profile_options())
        try:
            win = (path.window(seconds) if rate is None
                   else path.window(seconds, rate=rate))
        finally:
            jax.profiler.stop_trace()
        shutil.copy(trace.find_xplane(d), out / f"{cell.name}.xplane.pb")
    (out / f"{cell.name}.json").write_text(json.dumps(
        {"facts": win["facts"], "config": cell.config,
         "traffic": cell.traffic}))
    path.release()
    return path.check()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    # the compile cache of run.py, so that a run after this one is warm
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from chipbench import run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    sweep = run.Cell("star2d_r2.sweep")
    sweep.config = dict(sweep.config, grid=SWEEP_GRID)
    serve = run.Cell("star2d_r2.ensemble")
    serve.traffic = dict(serve.traffic, **SERVE_CUT)
    devices = run.chips(1)
    for cell, kw in ((sweep, {}), (serve, {"rate": SERVE_RATE})):
        seconds = SWEEP_SECONDS if cell is sweep else SERVE_SECONDS
        print(cell.name, record(cell, seconds, out, devices, **kw),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
