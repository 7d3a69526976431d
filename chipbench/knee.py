#!/usr/bin/env python3
"""The served cell's knee: the highest offered rate the server sustains.

    python chipbench/knee.py --workload star2d_r2.ensemble \
        --rates 100,200,300 --seconds 10 --seed 5

One process sets the cell's path up once, then runs its open-loop window
at each offered rate and prints, per rate, what completed within the
window, the median and 95th-percentile latency, and the 95th percentile
of the requests due in each half of the window: a backlog that grows
over the window shows as a second half far above the first.  Before
each window the garbage of what ran before is collected and frozen, as a
benchmark run does after its set-up, so that no collection of it pauses
the host path.  The knee is the served path's capacity, which PERF.md
gives beside the cells' loads; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="e.g. 100,200,300")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from chipbench import run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = run.Cell(args.workload)
    devices = run.chips(cell.chips)
    path = cell.path.Path(cell.config, cell.traffic, args.seed, devices)
    path.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        gc.collect()
        gc.freeze()
        win = path.window(args.seconds, rate=rate)
        f = win["facts"]
        print(json.dumps({
            "offered_per_s": rate,
            "completed_per_s": win["metrics"]["serve_rate"],
            "failed": win["failed"],
            "p50_ms": win["metrics"]["serve_p50_ms"],
            "p95_ms": win["metrics"]["serve_p95_ms"],
            "p95_ms_by_half": f["p95_ms_by_half"],
            "batches": f["batches"], "requests": f["requests"],
            "padded_states": f["padded_states"],
            "lag_p95_ms": f["lag_p95_ms"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
