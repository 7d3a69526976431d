"""Varying-coefficient / masked-domain scenario benchmark (modelled).

For each PAPER_SUITE cell in CELLS three plans are compared at the model
grids: the constant base spec, the same spec with a seeded per-point
coefficient field (``random_coeff_field``), and with a seeded ~70%-active
domain mask (``random_domain_mask``).  Recorded per cell:

* the planner's chosen (depth, strategy, block) for each scenario — the
  varying/masked rows may legally differ (operator fusion beyond depth 1
  is excluded for them, see DESIGN.md §Scenarios);
* the modelled per-state-per-step cost tax of the aux coefficient band
  (``varying_tax = t_vary / t_const``, >= 1 by construction: the field
  band is pure extra HBM traffic);
* the masked-block skip fraction — the share of output tiles whose mask
  is identically zero (skippable), reported both at the plan's chosen
  block and at a fixed fine tile (FINE_BLOCK) that exposes the mask's
  obstacle structure independently of the block search (large chosen
  blocks rarely go fully dead).

    PYTHONPATH=src python benchmarks/bench_varying.py            # table
    PYTHONPATH=src python benchmarks/bench_varying.py --json [--out ...]
    PYTHONPATH=src python benchmarks/bench_varying.py --smoke    # tier-1

``make bench-smoke`` runs the ``--json`` form so every PR leaves a
diffable trajectory point in ``BENCH_varying.json``.
"""
import argparse
import json

from repro import api
from repro.core import matrixization as mx
from repro.core import temporal
from repro.launch.compile_cache import enable_compile_cache

BENCH_VERSION = 1

MODEL_GRID_2D = (256, 256)
MODEL_GRID_3D = (64, 64, 64)
MODEL_STEPS = 16
MODEL_MAX_DEPTH = 4
MASK_ACTIVE = 0.7
FINE_BLOCK_2D = (16, 16)
FINE_BLOCK_3D = (8, 8, 8)
CELLS = ("box2d_r1", "star2d_r1", "star2d_r2", "box3d_r1", "star3d_r1")


def _chosen_row(spec, grid, boundary="periodic"):
    problem = api.StencilProblem(spec, grid, boundary=boundary,
                                 steps=MODEL_STEPS)
    p = api.plan(problem, max_depth=MODEL_MAX_DEPTH)
    c = p.chosen()
    # the candidate table itself must be legal — recheck, not trust
    for cand in p.candidates:
        assert temporal.fusion_legal(spec, boundary, cand.strategy,
                                     cand.depth), (cand.strategy, cand.depth)
    return p, {"depth": c.depth, "strategy": c.strategy,
               "backend": c.backend, "block": list(c.block),
               "t_per_step": c.t_per_step}


def model_cells(cells=CELLS):
    """Modelled constant-vs-varying-vs-masked decision per cell."""
    suite = api.PAPER_SUITE()
    rows = []
    for name in cells:
        spec = suite[name]
        grid = MODEL_GRID_2D if spec.ndim == 2 else MODEL_GRID_3D
        field = api.random_coeff_field(grid, seed=1)
        mask = api.random_domain_mask(grid, seed=2, active=MASK_ACTIVE)

        _, const = _chosen_row(spec, grid)
        _, vary = _chosen_row(spec.with_field(field), grid)
        _, msk = _chosen_row(spec.with_mask(mask), grid)

        vblock = tuple(vary["block"])
        mblock = tuple(msk["block"])
        fine = FINE_BLOCK_2D if spec.ndim == 2 else FINE_BLOCK_3D
        rows.append({
            "cell": name, "spec": spec.describe(), "grid": list(grid),
            "steps": MODEL_STEPS,
            "constant": const, "varying": vary, "masked": msk,
            "varying_tax": vary["t_per_step"] / const["t_per_step"],
            "aux_band_bytes_per_block": mx.aux_hbm_bytes(
                vblock, vary["depth"] * spec.order, 1),
            "masked_active_fraction": mx.active_block_fraction(mask, mblock),
            "masked_skip_fraction": 1.0 - mx.active_block_fraction(
                mask, fine),
        })
    return rows


def emit_json(path="BENCH_varying.json"):
    rows = model_cells()
    assert len(rows) >= 4, "acceptance: >= 4 scenario variants recorded"
    data = {
        "bench_version": BENCH_VERSION,
        "plan_version": api.PLAN_VERSION,
        "hw": "tpu_v5e",
        "mask_active": MASK_ACTIVE,
        "cells": rows,
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    taxes = ", ".join(f"{r['cell']}={r['varying_tax']:.3f}x" for r in rows)
    print(f"wrote {path}: {len(rows)} cells; varying traffic tax {taxes}")
    return data


def smoke():
    """Model-only tier-1 gate: the scenario pricing must be coherent —
    a coefficient band is never free, a ~70%-active mask always leaves
    skippable blocks, and no scenario plan carries an illegal pair."""
    rows = model_cells()
    for r in rows:
        print(f"{r['cell']}: tax={r['varying_tax']:.3f}x "
              f"vary=({r['varying']['strategy']},d{r['varying']['depth']}) "
              f"skip={r['masked_skip_fraction']:.2f}")
        assert r["varying_tax"] >= 1.0, r
        assert r["aux_band_bytes_per_block"] > 0, r
        assert 0.0 < r["masked_skip_fraction"] < 1.0, r
        assert 0.0 < r["masked_active_fraction"] <= 1.0, r
    assert len(rows) >= 4
    print(f"SMOKE PASS: {len(rows)} scenario cells priced coherently")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable BENCH_varying.json")
    ap.add_argument("--out", default="BENCH_varying.json")
    ap.add_argument("--smoke", action="store_true",
                    help="model-only pricing-coherence gate (tier-1)")
    args = ap.parse_args()
    if args.smoke:
        smoke()
        return
    if args.json:
        emit_json(args.out)
        return
    print("cell,varying_tax,vary_strategy,vary_depth,masked_skip_fraction")
    for r in model_cells():
        print(f"{r['cell']},{r['varying_tax']:.3f},"
              f"{r['varying']['strategy']},{r['varying']['depth']},"
              f"{r['masked_skip_fraction']:.3f}")


if __name__ == "__main__":
    main()
