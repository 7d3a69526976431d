"""Rollout-program benchmark: fused segment sweeps vs one-step-at-a-time.

For each cell in CELLS a canonical 3-segment program (prediction window
with a forcing source, short nudged hop, long free run) is planned at
the model grids and the :class:`repro.rollout.RolloutPlan` traffic model
is recorded: modelled HBM bytes per state for the program as planned
(each segment fused to its chosen depth) against the SAME program
executed one step at a time.  Update points are fusion barriers, so this
is the paper's T-fold traffic cut applied per segment — the acceptance
headline is the count of cells with a strict modelled per-state traffic
win (must be >= 2).

A measured section then compiles the program at a small grid and times
the fused :class:`~repro.rollout.CompiledRollout` against a stepwise
loop of depth-1 executables plus jitted updates (same arithmetic, no
in-segment fusion).  CPU-interpret magnitudes, but the ratio is the
wall-clock side of the traffic model.

    PYTHONPATH=src python benchmarks/bench_rollout.py            # table
    PYTHONPATH=src python benchmarks/bench_rollout.py --json [--out ...]
    PYTHONPATH=src python benchmarks/bench_rollout.py --smoke    # tier-1

``make bench-smoke`` runs the ``--json`` form so every PR leaves a
diffable trajectory point in ``BENCH_rollout.json``.
"""
import argparse
import dataclasses
import json
import time

import numpy as np

from repro import api
from repro.launch.compile_cache import enable_compile_cache
from repro.rollout.program import build_update

BENCH_VERSION = 1

MODEL_GRID_2D = (256, 256)
MODEL_GRID_3D = (64, 64, 64)
MODEL_BATCH = 4
CELLS = ("box2d_r1", "star2d_r2", "star3d_r2")

MEASURE_CELLS = ("box2d_r1", "star2d_r2")
MEASURE_GRID = (48, 48)
MEASURE_BATCH = 2
MEASURE_REPEATS = 3


def model_segments():
    """The canonical benchmark program: forced prediction window, short
    assimilation-style hop, long free run."""
    return (
        api.Segment(8, api.UpdateOp("source", {"scale": 0.1, "seed": 1}),
                    emit=True),
        api.Segment(4, api.UpdateOp("nudge", {"gain": 0.25, "seed": 2})),
        api.Segment(16, emit=True),
    )


def measure_segments():
    return (
        api.Segment(4, api.UpdateOp("source", {"scale": 0.1, "seed": 1}),
                    emit=True),
        api.Segment(2, api.UpdateOp("nudge", {"gain": 0.25, "seed": 2})),
        api.Segment(6),
    )


def _program(spec, grid, segments, batch):
    problem = api.StencilProblem(spec, grid, boundary="periodic",
                                 steps=1, batch=batch)
    return api.RolloutProgram(problem, segments)


def model_cells(cells=CELLS, batch=MODEL_BATCH):
    """Modelled fused-vs-stepwise traffic for the canonical program."""
    suite = api.PAPER_SUITE()
    rows = []
    for name in cells:
        spec = suite[name]
        grid = MODEL_GRID_2D if spec.ndim == 2 else MODEL_GRID_3D
        program = _program(spec, grid, model_segments(), batch)
        rplan = api.plan_program(program)
        t = rplan.traffic()
        fused_t = sum(p.chosen().t_per_step * p.steps
                      for p in rplan.segment_plans)
        rows.append({
            "cell": name, "spec": spec.describe(), "grid": list(grid),
            "batch": batch, "total_steps": program.total_steps,
            "segments": [{"steps": p.steps, "strategy": p.fuse_strategy,
                          "depth": p.fuse_depth,
                          "schedule": p.schedule_str(),
                          "backend": p.backend, "block": list(p.block)}
                         for p in rplan.segment_plans],
            "fused_mb_per_state": t["fused_bytes_per_state"] / 1e6,
            "stepwise_mb_per_state": t["stepwise_bytes_per_state"] / 1e6,
            "traffic_ratio": t["traffic_ratio"],
            "traffic_win": t["traffic_ratio"] > 1.0,
            "modelled_s_per_state": fused_t,
        })
    return rows


def _stepwise_fns(program):
    """Depth-1 executables + jitted updates: the unfused baseline with
    the segment plans' own backends."""
    import jax
    fns = []
    for i, seg in enumerate(program.segments):
        pb1 = dataclasses.replace(program.segment_problem(i), steps=1)
        one = api.compile(api.plan(pb1))
        up = (jax.jit(build_update(seg.update, program.segment_problem(i)))
              if seg.update is not None else None)
        fns.append((seg.steps, one.fn, up))
    return fns


def _time(fn, repeats=MEASURE_REPEATS):
    import jax
    jax.block_until_ready(fn())            # warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def measure_rollout(cells=MEASURE_CELLS):
    """Warm wall clock: fused compiled program vs the stepwise loop."""
    suite = api.PAPER_SUITE()
    rng = np.random.default_rng(0)
    out = {}
    for name in cells:
        program = _program(suite[name], MEASURE_GRID, measure_segments(),
                           MEASURE_BATCH)
        x = rng.normal(size=(MEASURE_BATCH,) + MEASURE_GRID).astype(
            np.float32)
        compiled = api.compile_program(program)
        fns = _stepwise_fns(program)

        def stepwise():
            y = x
            for steps, one, up in fns:
                for _ in range(steps):
                    y = one(y)
                if up is not None:
                    y = up(y)
            return y

        fused_s = _time(lambda: compiled.run(x).final)
        step_s = _time(stepwise)
        out[name] = {
            "grid": list(MEASURE_GRID), "batch": MEASURE_BATCH,
            "total_steps": program.total_steps,
            "fused_wall_ms": fused_s * 1e3,
            "stepwise_wall_ms": step_s * 1e3,
            "speedup": step_s / fused_s,
        }
    return out


def emit_json(path="BENCH_rollout.json"):
    cells = model_cells()
    wins = sorted(c["cell"] for c in cells if c["traffic_win"])
    assert len(wins) >= 2, f"modelled traffic win on only {wins}"
    data = {
        "bench_version": BENCH_VERSION,
        "plan_version": api.PLAN_VERSION,
        "hw": "tpu_v5e",
        "batch": MODEL_BATCH,
        "cells": cells,
        "traffic_wins": wins,
        "n_traffic_wins": len(wins),
        "measured": measure_rollout(),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}: modelled per-state traffic win on "
          f"{len(wins)}/{len(cells)} cells ({', '.join(wins)})")
    return data


def smoke():
    """Model-only tier-1 gate: the fused program must model a strict
    per-state traffic win on >= 2 cells."""
    rows = model_cells()
    wins = [r["cell"] for r in rows if r["traffic_win"]]
    for r in rows:
        print(f"{r['cell']}: {r['stepwise_mb_per_state']:.1f} MB/state "
              f"stepwise -> {r['fused_mb_per_state']:.1f} MB/state fused "
              f"({r['traffic_ratio']:.2f}x)")
    assert len(wins) >= 2, f"traffic win on only {wins}"
    print(f"SMOKE PASS: traffic win on {len(wins)}/{len(rows)} cells")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable BENCH_rollout.json")
    ap.add_argument("--out", default="BENCH_rollout.json")
    ap.add_argument("--smoke", action="store_true",
                    help="model-only traffic-win gate (tier-1)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    if args.json:
        emit_json(args.out)
        return
    print("cell,stepwise_mb_per_state,fused_mb_per_state,traffic_ratio,"
          "depths")
    for r in model_cells():
        depths = "/".join(str(s["depth"]) for s in r["segments"])
        print(f"{r['cell']},{r['stepwise_mb_per_state']:.1f},"
              f"{r['fused_mb_per_state']:.1f},{r['traffic_ratio']:.3f},"
              f"{depths}")


if __name__ == "__main__":
    main()
