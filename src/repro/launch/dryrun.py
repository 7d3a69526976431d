import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell on the production meshes; record memory/cost/collective analyses.

THE TWO LINES ABOVE MUST STAY FIRST: jax locks the device count at first
initialization, and the production meshes need 512 placeholder devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b --cell train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_results/
Each invocation is a fresh process (the launcher shells out per cell so a
single giant compile can't wedge the sweep and RAM is returned between
cells).
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPE_CELLS, cells_for, get_config
from repro.launch.cells import MODEL_FLOPS, build_cell
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import TPU_V5E, make_production_mesh
from repro.sharding import rules


def run_cell(arch: str, cell_name: str, multi_pod: bool, ce_chunk: int = 512,
             save_hlo: str | None = None) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = SHAPE_CELLS[cell_name]
    record = {
        "arch": arch, "cell": cell_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "devices": int(len(jax.devices())),
    }
    spec = build_cell(arch, cell_name, mesh)
    with rules.activate(mesh):
        jitted = jax.jit(spec.fn, in_shardings=spec.in_shardings,
                         out_shardings=spec.out_shardings,
                         donate_argnums=spec.donate)
        lowered = jitted.lower(*spec.args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    hlo = analyze_hlo(text)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(text)

    cfg = get_config(arch)
    n_dev = len(jax.devices())
    record.update({
        "lower_s": round(t_lower - t0, 2),
        "compile_s": round(t_compile - t_lower, 2),
        "hlo_bytes": len(text),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        "xla_cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "hlo_cost": hlo.to_json(),
        "model_flops_global": MODEL_FLOPS(cfg, cell),
        "params": cfg.param_count(),
    })

    # roofline terms (per device, single-pod basis)
    hw = TPU_V5E
    record["roofline"] = {
        "compute_s": hlo.dot_flops / hw.peak_flops_bf16,
        "memory_s": hlo.traffic_bytes / hw.hbm_bw,
        "collective_s": hlo.total_collective_bytes / hw.ici_bw,
    }
    terms = record["roofline"]
    record["roofline"]["bound"] = max(terms, key=lambda k: terms[k])
    mf_per_dev = record["model_flops_global"] / n_dev
    record["roofline"]["model_flops_per_dev"] = mf_per_dev
    record["roofline"]["useful_ratio"] = (
        mf_per_dev / hlo.dot_flops if hlo.dot_flops else None)
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="dryrun_results")
    ap.add_argument("--ce-chunk", type=int, default=512)
    ap.add_argument("--save-hlo", default=None)
    ap.add_argument("--stencil-plans", action="store_true",
                    help="print the stencil planner's PAPER_SUITE report "
                         "(modelled roofline decisions) and exit")
    ap.add_argument("--stencil-calibrate", action="store_true",
                    help="compile + measure the stencil calibration suite "
                         "and emit the result in the CalibrationRecord JSON "
                         "shape (the exact serializer repro.launch.calibrate "
                         "uses, so the output feeds plan(calibration=...) "
                         "and plan_report --calibration directly)")
    ap.add_argument("--calibration-out", default=None, metavar="JSON_PATH",
                    help="with --stencil-calibrate: write the record here "
                         "instead of stdout")
    args = ap.parse_args()

    if args.stencil_plans:
        from repro.launch.plan_report import generate_report
        print(generate_report(), end="")
        return
    if args.stencil_calibrate:
        # Measured costs in the exact CalibrationRecord shape — ONE
        # serializer shared with repro.launch.calibrate, not a parallel
        # print format.
        from repro.launch.calibrate import calibrate_suite
        text = calibrate_suite(wall=True).to_json(indent=1)
        if args.calibration_out:
            with open(args.calibration_out, "w") as f:
                f.write(text)
            print(f"wrote {args.calibration_out}")
        else:
            print(text)
        return

    os.makedirs(args.out, exist_ok=True)
    jobs = []
    if args.all:
        for arch in ARCH_IDS:
            for cell in cells_for(arch):
                jobs.append((arch, cell, False))
                jobs.append((arch, cell, True))
    else:
        meshes = [False, True] if args.both_meshes else [args.multi_pod]
        for mp in meshes:
            jobs.append((args.arch, args.cell, mp))

    failures = 0
    for arch, cell, mp in jobs:
        tag = f"{arch}__{cell}__{'pod2' if mp else 'pod1'}"
        out_path = os.path.join(args.out, tag + ".json")
        if os.path.exists(out_path):
            print(f"[skip] {tag}", flush=True)
            continue
        print(f"[run ] {tag}", flush=True)
        try:
            rec = run_cell(arch, cell, mp, ce_chunk=args.ce_chunk,
                           save_hlo=args.save_hlo)
            with open(out_path, "w") as f:
                json.dump(rec, f, indent=1)
            r = rec["roofline"]
            print(f"[ ok ] {tag}: compile={rec['compile_s']}s "
                  f"bound={r['bound']} compute={r['compute_s']:.4f}s "
                  f"mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures += 1
            with open(out_path + ".err", "w") as f:
                traceback.print_exc(file=f)
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
