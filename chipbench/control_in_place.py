#!/usr/bin/env python3
"""``control.py``'s readings for a sweep cell whose state leaves no room
for the control beside it.

    python chipbench/control_in_place.py --workload star2d_r2_49k.mesh \
        --seeds 101-112 --seconds 1

Per seed, as in ``control.py``, the cell's path sets up, runs its window
and checks it (the program's reading), then the control, the reference
at ``high``, is compared with the reference at the configuration's
precision (the control's reading).  Only where the control runs differs:
the reference's result waits on the host while the control evolves the
last call's input in place (its input donated), and the two are compared
on the host.  At 49152^2 on a (2,2) mesh of TPU v5e chips the control's
program reserves 11.28 GiB of temporaries per chip, which leaves no room
for a second state: beside an input and an output 11.25 GiB are free, and
after it has run a buffer of 2.25 GiB no longer fits.  The readings are
``control.py``'s, to the bit (``tests/test_control_in_place.py``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: rows of the host comparison at a time (768 MiB of a 49152-wide state)
BAND = 4096


def control_reading(path) -> dict:
    """The control's reading of a sweep ``path`` after its window, with
    the control evolved in place: the path's last input is used up."""
    import jax
    from chipbench import reference

    def evolved(x, precision):
        return reference.evolve(x, path.coeffs, path.steps, precision,
                                path.sharding)
    path.final = None
    ref = jax.device_get(evolved(path.last_in, path.config["precision"]))
    x, path.last_in = path.last_in, None
    got = jax.device_get(jax.jit(lambda a: evolved(a, "high"),
                                 donate_argnums=0)(x))
    return {"rel_err": rel_err_host(got, ref)}


def rel_err_host(y: np.ndarray, ref: np.ndarray) -> float:
    """``reference.rel_err`` on the host, a band of rows at a time: the
    same float32 differences and maxima, so the same number."""
    gap = scale = np.float32(0)
    for i in range(0, ref.shape[0], BAND):
        b = ref[i:i + BAND]
        gap = max(gap, np.max(np.abs(y[i:i + BAND] - b)))
        scale = max(scale, np.max(np.abs(b)))
    return float(gap) / max(float(scale), float(np.finfo(np.float32).tiny))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 101-112,200")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import types
    from chipbench import control, run
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = run.Cell(args.workload)
    if cell.traffic["path"] != "sweep":
        raise run.BenchError(f"{args.workload!r} is not a sweep cell")

    class Path(cell.path.Path):
        def check(self, control: bool = False) -> dict:
            return control_reading(self) if control else super().check()
    cell.path = types.SimpleNamespace(Path=Path)
    devices = run.chips(cell.chips)
    _, summary = control.measure(cell, control.seed_list(args.seeds),
                                 args.seconds, devices)
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
