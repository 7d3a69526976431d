"""Planner end-to-end: modelled decision vs measured wall-clock, with a
calibration round.

For each PAPER_SUITE cell, plan() the problem, compile() the winner, and
time it against the naive sequential engine run — the measured speedup
lands next to the modelled per-step roofline figures so cost-model drift
is visible (the CPU container measures XLA-CPU, the model measures
TPU_V5E; the *ranking* is what should agree).  Then run the measured-cost
calibration pass (launch.calibrate) over the plan's top candidates and
re-plan with the resulting record, reporting the per-backend factors and
whether the measured numbers re-ranked the decision.

    PYTHONPATH=src python benchmarks/bench_plan.py

``--json`` instead emits the machine-readable perf trajectory
``BENCH_plan.json``: the pure-model planner decision for EVERY PAPER_SUITE
cell at the plan-report grids (chosen strategy/depth/backend/block and
modelled cost per step, plus the best deep-fusion cost per strategy so the
operator-vs-inkernel gap is recorded), and the measured calibration
factors for a small cell subset.  ``make bench-smoke`` runs it so every PR
leaves a diffable trajectory point.

    PYTHONPATH=src python benchmarks/bench_plan.py --json [--out BENCH_plan.json]
"""
import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.core.engine import StencilEngine
from repro.launch.calibrate import calibrate_suite
from repro.launch.compile_cache import enable_compile_cache

# the plan-report cells (launch.plan_report): one shape-preserving
# evolution per paper spec
MODEL_GRID_2D = (256, 256)
MODEL_GRID_3D = (64, 64, 64)
MODEL_STEPS = 16
BENCH_VERSION = 1


def _time(fn, x, repeats=5):
    fn(x).block_until_ready()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(names=("box2d_r1", "star2d_r2"), n=256, steps=16, repeats=5):
    rows = []
    suite = api.PAPER_SUITE()
    for name in names:
        spec = suite[name]
        x = jnp.asarray(np.random.default_rng(0).normal(size=(n,) * spec.ndim),
                        jnp.float32)
        problem = api.StencilProblem(spec, (n,) * spec.ndim,
                                     boundary="periodic", steps=steps)
        p = api.plan(problem, backends=["jnp"])  # interpretable on CPU
        compiled = api.compile(p)
        eng = StencilEngine(spec, boundary="periodic")
        seq = jax.jit(lambda a, s=steps: eng.run(a, steps=s))
        fused = jax.jit(compiled.fn)
        t_seq = _time(seq, x, repeats)
        t_fused = _time(fused, x, repeats)
        err = float(jnp.abs(seq(x) - fused(x)).max())
        ch = p.chosen()

        # calibration round: measure the top candidates, re-rank the table
        record = api.calibrate(problem, top_k=2, backends=["jnp"])
        p_cal = api.plan(problem, backends=["jnp"], calibration=record)
        cal = p_cal.chosen()
        rows.append({
            "name": name, "depth": p.fuse_depth, "cover": p.option,
            "strategy": p.fuse_strategy,
            "backend": p.backend, "block": "x".join(map(str, p.block)),
            "t_seq_us": t_seq * 1e6, "t_plan_us": t_fused * 1e6,
            "speedup": t_seq / t_fused,
            "model_step_ns": ch.t_per_step * 1e9,
            "max_err": err,
            "cal_traffic_factor": record.traffic.get(p.backend, 1.0),
            "cal_depth": p_cal.fuse_depth,
            "cal_block": "x".join(map(str, p_cal.block)),
            "cal_step_ns": cal.t_per_step * 1e9,
            "reranked": (p_cal.fuse_depth, p_cal.option, p_cal.backend,
                         p_cal.block) != (p.fuse_depth, p.option, p.backend,
                                          p.block),
        })
    return rows


def model_suite(steps=MODEL_STEPS, max_depth=4):
    """Pure-model trajectory: plan() every PAPER_SUITE cell, no compilation.

    ``best_*_deep`` record the cheapest modelled per-step cost among
    depth>=2 rows of each strategy, so the JSON captures the
    operator-vs-inkernel gap (the acceptance headline: flops linear in T)
    even on cells where depth 1 wins outright.
    """
    rows = []
    suite = api.PAPER_SUITE()
    for name in sorted(suite):
        spec = suite[name]
        grid = MODEL_GRID_2D if spec.ndim == 2 else MODEL_GRID_3D
        problem = api.StencilProblem(spec, grid, boundary="periodic",
                                     steps=steps)
        p = api.plan(problem, max_depth=max_depth)
        ch = p.chosen()
        best = {}
        for strat in api.FUSE_STRATEGIES:
            deep = [c.t_per_step for c in p.candidates
                    if c.strategy == strat and c.depth >= 2]
            best[strat] = min(deep) if deep else None
        rows.append({
            "cell": name, "spec": spec.describe(), "grid": list(grid),
            "strategy": p.fuse_strategy, "depth": p.fuse_depth,
            "backend": p.backend, "cover": p.option, "block": list(p.block),
            "t_per_step_s": ch.t_per_step,
            "best_operator_deep_s": best["operator"],
            "best_inkernel_deep_s": best["inkernel"],
            "inkernel_wins_deep": (best["inkernel"] is not None
                                   and best["operator"] is not None
                                   and best["inkernel"] < best["operator"]),
        })
    return rows


def emit_json(path="BENCH_plan.json", steps=MODEL_STEPS,
              calibrate_cells=("box2d_r1", "star2d_r2")):
    cells = model_suite(steps=steps)
    record = calibrate_suite(names=calibrate_cells, grid=(48, 48), steps=4,
                             backends=("jnp",), top_k=1)
    data = {
        "bench_version": BENCH_VERSION,
        "plan_version": api.PLAN_VERSION,
        "hw": "tpu_v5e",
        "steps": steps,
        "cells": cells,
        "inkernel_wins": sorted(c["cell"] for c in cells
                                if c["inkernel_wins_deep"]),
        "chosen_inkernel": sorted(c["cell"] for c in cells
                                  if c["strategy"] == "inkernel"),
        "calibration": {"cells": list(calibrate_cells),
                        "compute": record.compute,
                        "traffic": record.traffic},
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}: {len(cells)} cells, "
          f"{len(data['chosen_inkernel'])} chose inkernel, "
          f"{len(data['inkernel_wins'])} inkernel deep-fusion wins")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable BENCH_plan.json "
                         "trajectory instead of the wall-clock CSV")
    ap.add_argument("--out", default="BENCH_plan.json")
    args = ap.parse_args()
    if args.json:
        emit_json(args.out)
        return
    print("name,depth,cover,strategy,backend,block,t_seq_us,t_plan_us,"
          "cpu_speedup,v5e_model_step_ns,max_err,cal_traffic_factor,"
          "cal_depth,cal_block,cal_step_ns,reranked")
    for r in run():
        print(f"{r['name']},{r['depth']},{r['cover']},{r['strategy']},"
              f"{r['backend']},{r['block']},"
              f"{r['t_seq_us']:.0f},{r['t_plan_us']:.0f},{r['speedup']:.2f},"
              f"{r['model_step_ns']:.1f},{r['max_err']:.1e},"
              f"{r['cal_traffic_factor']:.2f},{r['cal_depth']},"
              f"{r['cal_block']},{r['cal_step_ns']:.1f},{r['reranked']}")


if __name__ == "__main__":
    main()
