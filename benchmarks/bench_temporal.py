"""Beyond-paper: temporal fusion (paper §6 future work) through the fused
sweep pipeline — ``StencilEngine.sweep`` vs T sequential sweeps, measured
wall-clock plus the roofline model the fuse-depth chooser runs on.

The modelled HBM-traffic column is the acceptance headline: one fused
T-step sweep reads the (haloed) grid once and writes it once instead of T
times, so the modelled reduction approaches T (and stays >= T/2 even with
the fused halo overhead at paper-scale blocks).

``--json`` emits the machine-readable trajectory ``BENCH_temporal.json``
(the same rows plus the strategy-aware chooser's operator-vs-inkernel
modelled flop ratios per configuration); ``make bench-smoke`` runs it.
"""
import argparse
import json
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import stencil_spec as ss
from repro.core.engine import StencilEngine
from repro.core.temporal import (FUSE_STRATEGIES, choose_fuse_depth,
                                 fused_flops_ratio, inkernel_flops_ratio)
from repro.launch.compile_cache import enable_compile_cache

BENCH_VERSION = 1


def _time(fn, x, repeats=5):
    fn(x).block_until_ready()
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def run(sizes=(256, 512), steps_list=(2, 4, 8), repeats=5, boundary="periodic"):
    rows = []
    spec = ss.star(2, 1, seed=1)
    for n in sizes:
        x = jnp.asarray(np.random.default_rng(0).normal(size=(n, n)),
                        jnp.float32)
        eng = StencilEngine(spec, boundary=boundary)
        for steps in steps_list:
            dec = choose_fuse_depth(spec, steps, block=eng.plan.block)
            cand = dec.candidate(dec.depth)
            # strategy-aware model at the paper-scale block (execution
            # below stays on the jnp engine; this records what the
            # in-kernel Pallas strategy would be modelled to do)
            dec2 = choose_fuse_depth(spec, steps, block=eng.plan.block,
                                     strategies=FUSE_STRATEGIES)
            seq = jax.jit(lambda x, s=steps: eng.run(x, steps=s))
            fus = jax.jit(eng.sweep_fn(steps, fuse=steps))
            auto = jax.jit(eng.sweep_fn(steps, fuse="auto"))
            t_seq = _time(seq, x, repeats)
            t_fus = _time(fus, x, repeats)
            t_auto = _time(auto, x, repeats)
            err = float(jnp.abs(seq(x) - fus(x)).max())
            rows.append({
                "n": n, "steps": steps,
                "t_seq_us": t_seq * 1e6, "t_fused_us": t_fus * 1e6,
                "t_auto_us": t_auto * 1e6,
                "speedup": t_seq / t_fus,
                "auto_depth": dec.depth,
                "model_strategy": dec2.strategy,
                "model_strategy_depth": dec2.depth,
                "flops_ratio_model": fused_flops_ratio(spec, steps, n),
                "inkernel_flops_ratio_model": inkernel_flops_ratio(
                    spec, steps, n),
                # modelled HBM traffic per original step at full fusion
                # (the deepest candidate, i.e. depth min(steps, max_depth))
                "traffic_reduction_model":
                    dec.candidates[-1].traffic_reduction,
                "v5e_step_time_model_us": cand.t_per_step * 1e6,
                "max_err": err,
            })
    return rows


def emit_json(path="BENCH_temporal.json"):
    rows = run()
    data = {
        "bench_version": BENCH_VERSION,
        "rows": rows,
        "traffic_headline_ok": any(
            r["traffic_reduction_model"] >= r["steps"] / 2 for r in rows),
    }
    with open(path, "w") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}: {len(rows)} rows")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable BENCH_temporal.json "
                         "trajectory instead of the wall-clock CSV")
    ap.add_argument("--out", default="BENCH_temporal.json")
    args = ap.parse_args()
    if args.json:
        emit_json(args.out)
        return None
    print("n,steps,t_seq_us,t_fused_us,t_auto_us,cpu_speedup,auto_depth,"
          "model_strategy,traffic_reduction_model,max_err")
    ok = False
    for r in run():
        print(f"{r['n']},{r['steps']},{r['t_seq_us']:.0f},{r['t_fused_us']:.0f},"
              f"{r['t_auto_us']:.0f},{r['speedup']:.2f},{r['auto_depth']},"
              f"{r['model_strategy']},"
              f"{r['traffic_reduction_model']:.2f},{r['max_err']:.1e}")
        if r["traffic_reduction_model"] >= r["steps"] / 2:
            ok = True
    print("modelled >=T/2-fold HBM-traffic reduction achieved "
          f"for at least one fused configuration: {ok}")
    return None


if __name__ == "__main__":
    main()
