#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's and the control's.

    python chipbench/control.py --workload star2d_r2.sweep \
        --seeds 101-112 --seconds 10

For each seed, in one process, the cell's path sets up and runs its
window as a benchmark run does, then the check compares what the window
produced with the reference (the program's reading) and compares the
control, the reference computed at ``high`` (three bf16 passes) put in
the program's place, with the same reference (the control's reading).
One line per seed, then a JSON summary with the largest program reading
and the smallest control reading of each number the check returns.  It
runs on a cell listed in ``BENCHMARK.json`` before the cell has a limit
file; the benchmark's own runs never run this, and
``limits/<workload>.json`` records what it read.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def seed_list(text: str) -> list[int]:
    out = []
    for part in text.split(","):
        a, _, b = part.partition("-")
        out += list(range(int(a), int(b) + 1)) if b else [int(a)]
    return out


def readings(cell, seed: int, seconds: float, devices) -> dict:
    """The program's and the control's compared numbers for one seed."""
    path = cell.path.Path(cell.config, cell.traffic, seed, devices)
    path.setup()
    path.window(seconds)
    path.release()
    program = path.check()
    control = path.check(control=True)
    return {"seed": seed, "program": program, "control": control}


def measure(cell, seeds, seconds: float, devices) -> tuple[list, dict]:
    """The readings of every seed, each printed as it comes, and their
    summary: for each number the path's check returns, the largest
    program reading and the smallest control reading.  The cell needs no
    limit file: these readings are what its limits are set from."""
    rows = []
    for seed in seeds:
        row = readings(cell, seed, seconds, devices)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for name in rows[0]["program"]:
        summary[name] = {
            "program_max": max(r["program"][name] for r in rows),
            "control_min": min(r["control"][name] for r in rows),
            "seeds": len(rows)}
    return rows, summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,200")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from chipbench import run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = run.Cell(args.workload)
    devices = run.chips(cell.chips)
    _, summary = measure(cell, seed_list(args.seeds), args.seconds, devices)
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
