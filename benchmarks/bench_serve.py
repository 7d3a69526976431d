"""Batched serving benchmark: modelled per-state cost vs batch size, plus
measured serving-loop throughput and sync-vs-async dispatch wall clock.

For every PAPER_SUITE cell the planner is run at the plan-report grids
for B in BATCHES and the chosen candidate's per-STATE per-step cost is
recorded (``CandidateCost.t_per_step`` is already per state, so the
B-curve directly shows what batch-in-M buys: MXU M-fill on compute-bound
cells, launch amortization everywhere).  The acceptance headline is the
count of cells where B=8 is strictly cheaper per state than B=1.

A measured section then drives the real serving loop
(``launch.serve_stencil.StencilServer``) on a small cell subset at
max_batch in MEASURE_BATCHES, in BOTH dispatch modes — synchronous
(settle each bucket before dispatching the next) and asynchronous
continuous batching (host-side stacking of bucket N+1 overlapped with
device execution of bucket N) — recording warm whole-stream wall clock,
warm per-state wall clock and p50/p95 submit->result latency.  On this
CPU container the numbers are XLA-CPU magnitudes, but the sync/async
ratio is the dispatch overlap the server exists to provide.

An admission section records the planner's bucket-cliff query at the
model grids: per cell the modelled per-state curve over the serving
buckets and the cap ``max_profitable_batch`` returns — the star3d cells
demonstrably cap below max_batch (the batch-scaled VMEM cliff).

    PYTHONPATH=src python benchmarks/bench_serve.py --json [--out BENCH_serve.json]
    PYTHONPATH=src python benchmarks/bench_serve.py --async   # measured table
    PYTHONPATH=src python benchmarks/bench_serve.py --smoke   # tier-1 gate

``make bench-smoke`` runs the ``--json`` form so every PR leaves a
diffable trajectory point in ``BENCH_serve.json``.
"""
import argparse
import json
import time

import numpy as np

import jax.numpy as jnp

from repro import api
from repro.launch.compile_cache import enable_compile_cache

MODEL_GRID_2D = (256, 256)
MODEL_GRID_3D = (64, 64, 64)
MODEL_STEPS = 16
BATCHES = (1, 2, 4, 8)
BENCH_VERSION = 2

MEASURE_CELLS = ("box2d_r1", "star2d_r2")
MEASURE_GRID = (48, 48)
MEASURE_STEPS = 4
MEASURE_REQUESTS = 16
MEASURE_BATCHES = (1, 4, 8)

ADMISSION_CELLS = ("box2d_r1", "star3d_r2", "star3d_r3")


def model_cells(steps=MODEL_STEPS):
    """Modelled per-state cost per PAPER_SUITE cell across BATCHES."""
    rows = []
    suite = api.PAPER_SUITE()
    for name in sorted(suite):
        spec = suite[name]
        grid = MODEL_GRID_2D if spec.ndim == 2 else MODEL_GRID_3D
        per_state = {}
        chosen = {}
        for b in BATCHES:
            p = api.plan(api.StencilProblem(spec, grid, boundary="periodic",
                                            steps=steps, batch=b))
            ch = p.chosen()
            per_state[b] = ch.t_per_step
            chosen[b] = {"strategy": p.fuse_strategy, "depth": p.fuse_depth,
                         "backend": p.backend, "block": list(p.block)}
        rows.append({
            "cell": name, "spec": spec.describe(), "grid": list(grid),
            "per_state_s": {str(b): per_state[b] for b in BATCHES},
            "speedup_b8": per_state[1] / per_state[8],
            "b8_wins": per_state[8] < per_state[1],
            "chosen_b1": chosen[1], "chosen_b8": chosen[8],
        })
    return rows


def _measure_pass(server, states):
    """(warm whole-stream wall seconds, warm stats) for one server."""
    server.serve(states)               # cold: plans + compiles
    server.reset_stats()               # so latency/throughput are warm-only
    t0 = time.perf_counter()
    server.serve(states)               # warm: pure cache hits
    wall = time.perf_counter() - t0
    s = server.stats()
    assert s["plan_cache"]["misses"] <= 2, s  # one executable per bucket shape
    return wall, s


def measure_serving(cells=MEASURE_CELLS, requests=MEASURE_REQUESTS,
                    batches=MEASURE_BATCHES):
    """Warm serving wall clock, sync vs async dispatch, across max_batch."""
    suite = api.PAPER_SUITE()
    rng = np.random.default_rng(0)
    out = {}
    for name in cells:
        spec = suite[name]
        states = [rng.normal(size=MEASURE_GRID).astype(np.float32)
                  for _ in range(requests)]
        row = {}
        for mb in batches:
            modes = {}
            for mode in ("sync", "async"):
                server = api.StencilServer(
                    spec, MEASURE_STEPS, max_batch=mb, backends=["jnp"],
                    async_dispatch=(mode == "async"))
                wall, s = _measure_pass(server, states)
                modes[mode] = {
                    "warm_wall_ms": wall * 1e3,
                    "warm_per_state_us": wall / requests * 1e6,
                    "p50_latency_ms": s["latency"]["p50_s"] * 1e3,
                    "p95_latency_ms": s["latency"]["p95_s"] * 1e3,
                }
            modes["async_speedup"] = (modes["sync"]["warm_wall_ms"]
                                      / modes["async"]["warm_wall_ms"])
            row[f"b{mb}"] = modes
        row["measured_amortization"] = (
            row[f"b{batches[0]}"]["async"]["warm_per_state_us"]
            / row[f"b{batches[-1]}"]["async"]["warm_per_state_us"])
        out[name] = row
    return out


def admission_report(cells=ADMISSION_CELLS, max_batch=8, steps=MODEL_STEPS):
    """The bucket-cliff query at the model grids: per cell the modelled
    per-state curve over the serving buckets and the admission cap
    (model-only; nothing is compiled)."""
    suite = api.PAPER_SUITE()
    out = {}
    for name in cells:
        spec = suite[name]
        grid = MODEL_GRID_2D if spec.ndim == 2 else MODEL_GRID_3D
        problem = api.StencilProblem(spec, grid, boundary="periodic",
                                     steps=steps)
        curve = api.batch_cost_curve(problem, max_batch)
        out[name] = {
            "grid": list(grid), "max_batch": max_batch,
            "cap": api.max_profitable_batch(problem, max_batch),
            "per_state_s": {str(b): curve[b] for b in sorted(curve)},
        }
    return out


def emit_json(path="BENCH_serve.json", steps=MODEL_STEPS):
    cells = model_cells(steps=steps)
    wins = sorted(c["cell"] for c in cells if c["b8_wins"])
    admission = admission_report(steps=steps)
    data = {
        "bench_version": BENCH_VERSION,
        "plan_version": api.PLAN_VERSION,
        "hw": "tpu_v5e",
        "steps": steps,
        "batches": list(BATCHES),
        "cells": cells,
        "b8_wins": wins,
        "n_b8_wins": len(wins),
        "measured": measure_serving(),
        "admission": admission,
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    capped = sorted(n for n, a in admission.items()
                    if a["cap"] < a["max_batch"])
    print(f"wrote {path}: {len(wins)}/{len(cells)} cells model a strict "
          f"per-state win at B=8; admission caps below max_batch on "
          f"{capped}")
    return data


def smoke():
    """Tiny end-to-end pass for the tier-1 gate: one measured cell in both
    dispatch modes plus the (model-only) admission query."""
    row = measure_serving(cells=("box2d_r1",), requests=6)["box2d_r1"]
    adm = admission_report(cells=("star3d_r2",))["star3d_r2"]
    assert adm["cap"] < adm["max_batch"], adm  # the VMEM cliff is capped
    b8 = row["b8"]
    print(f"box2d_r1 b8 warm per state: async "
          f"{b8['async']['warm_per_state_us']:.0f} us / sync "
          f"{b8['sync']['warm_per_state_us']:.0f} us "
          f"(p95 latency {b8['async']['p95_latency_ms']:.1f} ms); "
          f"star3d_r2 admission cap {adm['cap']} < {adm['max_batch']}")
    print("bench-serve smoke OK")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable BENCH_serve.json")
    ap.add_argument("--out", default="BENCH_serve.json")
    ap.add_argument("--async", dest="async_", action="store_true",
                    help="print the measured sync-vs-async serving table")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny measured + admission pass (the tier-1 gate)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    if args.json:
        emit_json(args.out)
        return
    if args.async_:
        print("cell,max_batch,sync_warm_ms,async_warm_ms,async_speedup,"
              "async_p50_ms,async_p95_ms")
        for name, row in measure_serving().items():
            for mb in MEASURE_BATCHES:
                m = row[f"b{mb}"]
                print(f"{name},{mb},{m['sync']['warm_wall_ms']:.1f},"
                      f"{m['async']['warm_wall_ms']:.1f},"
                      f"{m['async_speedup']:.2f},"
                      f"{m['async']['p50_latency_ms']:.2f},"
                      f"{m['async']['p95_latency_ms']:.2f}")
        return
    print("cell,per_state_ns_b1,per_state_ns_b8,speedup_b8,b8_wins,"
          "strategy_b8,depth_b8")
    for r in model_cells():
        ch = r["chosen_b8"]
        print(f"{r['cell']},{r['per_state_s']['1'] * 1e9:.1f},"
              f"{r['per_state_s']['8'] * 1e9:.1f},{r['speedup_b8']:.3f},"
              f"{r['b8_wins']},{ch['strategy']},{ch['depth']}")


if __name__ == "__main__":
    main()
