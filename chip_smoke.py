#!/usr/bin/env python3
"""Chip smoke test: the stencil engine's main paths on a TPU at chip size.

    python chip_smoke.py          # one chip: phases (a) planned sweeps,
                                  # (b) serving, (c) a rollout program
    python chip_smoke.py --mesh   # four chips: the fused distributed
                                  # stepper on a (2, 2) mesh vs one device

Every phase goes through the entry points a user calls (``api.plan`` ->
``api.compile``, ``StencilServer``, ``compile_program``) with the Pallas
backend compiled by Mosaic, and checks its output against the plain f32
gather oracle ``repro.kernels.ref.stencil_ref`` evolved on the device.
States are random normal, made on the device from ``--seed``.  Each phase
prints its max abs error, compile and run wall time (``block_until_ready``)
and the device's peak bytes in use.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every phase passed; any failure (no TPU, an unknown device kind,
an exceeded tolerance, a degraded or faulted server) exits non-zero first.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402

from repro import api  # noqa: E402
from repro.kernels.ref import REORDER_TOL, stencil_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import hardware_for_device, make_mesh  # noqa: E402
from repro.rollout.executor import compile_program  # noqa: E402
from repro.rollout.program import (RolloutProgram, Segment,  # noqa: E402
                                   UpdateOp)

PLATFORM = "tpu"
#: (a) a 1 GiB f32 state, over 4x the chip's on-chip memory, and a
#: 512 MiB 3-D state; (b)/(c) 64 MiB states, a bucket of 8 per call
SWEEP_GRID = (16384, 16384)
SWEEP_STEPS = 16
GRID_3D = (512, 512, 512)
STEPS_3D = 8
SERVE_GRID = (4096, 4096)
SERVE_BATCH = 8
SERVE_STEPS = 8
#: max abs error allowed against the f32 gather oracle: the repo's parity
#: bar (tests/parity.py); an f32 contraction run as a single bf16 pass
#: misses it by an order of magnitude or more
ORACLE_ATOL = 1e-4


def check(cond, msg: str) -> None:
    if not cond:
        sys.exit(f"chip_smoke: FAILED: {msg}")


def peak_gib(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 2 ** 30


def oracle(spec, x, segments):
    """The f32 gather oracle over ``segments`` = [(steps, update | None)]."""
    @jax.jit
    def run(a):
        for steps, update in segments:
            a = jax.lax.fori_loop(
                0, steps, lambda _, b: stencil_ref(b, spec,
                                                   boundary="periodic"), a)
            if update is not None:
                a = update(a)
        return a
    return run(x)


def max_err(y, ref) -> float:
    return float(jnp.max(jnp.abs(y.astype(jnp.float32)
                                 - ref.astype(jnp.float32))))


def planned_sweep(label, name, grid, steps, backends, x, dev, mesh=None):
    """plan -> compile -> run one periodic sweep, checked vs the oracle."""
    spec = api.PAPER_SUITE()[name]
    kw = {} if mesh is None else dict(mesh=mesh,
                                      grid_axes=tuple(mesh.axis_names))
    problem = api.StencilProblem(spec, grid, boundary="periodic",
                                 steps=steps, **kw)
    p = api.plan(problem, backends=backends)
    run = api.compile(p, mesh=mesh)
    if mesh is None:
        t0 = time.perf_counter()
        compiled = jax.jit(run.fn).lower(x).compile()
        t_compile = time.perf_counter() - t0
        t0 = time.perf_counter()
        y = jax.block_until_ready(compiled(x))
    else:
        st = run.stepper
        x = jax.device_put(x, NamedSharding(st.mesh, st.pspec))
        t0 = time.perf_counter()
        compiled = st.fn.lower(x).compile()
        t_compile = time.perf_counter() - t0
        jax.block_until_ready(run(x))          # the jit's own first call
        t0 = time.perf_counter()
        y = jax.block_until_ready(run(x))
    t_run = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()
    if p.backend == "pallas":
        check(kernel, f"{label}: the pallas plan's executable holds no "
                      f"Mosaic kernel")
    err = max_err(y, oracle(spec, x, [(steps, None)]))
    gib = 4 * int(np.prod(grid)) / 2 ** 30
    print(f"[{label}] {name} {'x'.join(map(str, grid))} f32 ({gib:.2f} "
          f"GiB/state) periodic, {steps} steps: backend={p.backend} "
          f"strategy={p.fuse_strategy} depth={p.fuse_depth} "
          f"block={p.block} mosaic_kernel={kernel} | compile "
          f"{t_compile:.2f} s, run {t_run:.4f} s, max|err| {err:.3e} "
          f"(atol {ORACLE_ATOL:g}), peak {peak_gib(dev):.2f} GiB")
    check(np.isfinite(err) and err <= ORACLE_ATOL,
          f"{label}: max error {err:.3e} > {ORACLE_ATOL:g}")
    return y


def phase_serve(key, dev) -> jnp.ndarray:
    """(b) one bucket of SERVE_BATCH states through StencilServer."""
    spec = api.PAPER_SUITE()["star2d_r2"]
    server = api.StencilServer(spec, SERVE_STEPS, max_batch=SERVE_BATCH,
                               backends=["pallas"], admission=False,
                               fallback_after=None)
    states = jax.random.normal(key, (SERVE_BATCH,) + SERVE_GRID,
                               jnp.float32)
    t0 = time.perf_counter()
    tickets = [server.submit(states[i]) for i in range(SERVE_BATCH)]
    out = server.flush()
    ys = jax.block_until_ready(jnp.stack([out[t] for t in tickets]))
    t_flush = time.perf_counter() - t0
    s = server.stats()
    err = max_err(ys, oracle(spec, states, [(SERVE_STEPS, None)]))
    faults = {k: v for k, v in s["faults"].items() if v}
    print(f"[b.serve] {SERVE_BATCH} x star2d_r2 "
          f"{'x'.join(map(str, SERVE_GRID))} f32, {SERVE_STEPS} steps: "
          f"{s['batches']} batch, cold flush {t_flush:.2f} s (first-call "
          f"compile {s['compile_wall_s']:.2f} s), max|err| {err:.3e}, "
          f"degraded={s['degraded']} faults={faults}, peak "
          f"{peak_gib(dev):.2f} GiB")
    check(err <= ORACLE_ATOL, f"serve: max error {err:.3e}")
    check(s["batches"] == 1, f"serve: {s['batches']} batches, not one "
                             f"bucket of {SERVE_BATCH}")
    check(not s["degraded"], f"serve: degraded groups {s['degraded']}")
    check(not faults, f"serve: fault counters {faults}")
    return states[0]


def phase_rollout(x, dev) -> None:
    """(c) a 3-segment program with an update between the sweeps."""
    spec = api.PAPER_SUITE()["star2d_r2"]
    problem = api.StencilProblem(spec, SERVE_GRID, boundary="periodic",
                                 steps=1)
    program = RolloutProgram(problem, [
        Segment(4, emit=True),
        Segment(4, UpdateOp("scale", {"factor": 0.5}), emit=True),
        Segment(4, emit=True)])
    t0 = time.perf_counter()
    compiled = compile_program(program, backends=["pallas"])
    jax.block_until_ready(compiled.run(x).final)   # compiles every segment
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    res = compiled.run(x)
    jax.block_until_ready(res.final)
    t_run = time.perf_counter() - t0

    def half(a):
        return 0.5 * a
    refs = [oracle(spec, x, [(4, None)]),
            oracle(spec, x, [(4, None), (4, half)]),
            oracle(spec, x, [(4, None), (4, half), (4, None)])]
    errs = [max_err(y, r) for (_, y), r in zip(res.emits, refs)]
    backends = sorted({p.backend for p in compiled.plan.segment_plans})
    print(f"[c.rollout] star2d_r2 {'x'.join(map(str, SERVE_GRID))} f32, "
          f"segments 4 | 4 + scale(0.5) | 4: backends={backends} | plan + "
          f"compile + first run {t_first:.2f} s, run {t_run:.4f} s, "
          f"max|err| per emit {', '.join(f'{e:.3e}' for e in errs)}, peak "
          f"{peak_gib(dev):.2f} GiB")
    check(len(errs) == 3 and max(errs) <= ORACLE_ATOL,
          f"rollout: emit errors {errs}")


def phase_mesh(key, devices) -> None:
    """The fused distributed stepper on a (2, 2) mesh vs one device."""
    check(len(devices) >= 4, f"--mesh needs 4 chips, found {len(devices)}")
    mesh = make_mesh((2, 2), ("gx", "gy"), devices=devices[:4])
    x = jax.random.normal(key, SWEEP_GRID, jnp.float32)
    y_mesh = planned_sweep("mesh", "star2d_r2", SWEEP_GRID, SWEEP_STEPS,
                           ["pallas"], x, devices[0], mesh=mesh)
    for s in y_mesh.addressable_shards:
        print(f"[mesh] shard {s.index} on device {s.device.id} "
              f"({s.device.device_kind})")
    shard_devs = {s.device.id for s in y_mesh.addressable_shards}
    check(len(shard_devs) == 4, f"mesh result spans devices "
                                f"{sorted(shard_devs)}, not 4")
    y_one = planned_sweep("single", "star2d_r2", SWEEP_GRID, SWEEP_STEPS,
                          ["pallas"], x, devices[0])
    a = jax.device_put(y_mesh, devices[0])
    diff = max_err(a, y_one)
    close = bool(jnp.all(jnp.abs(a - y_one) <= REORDER_TOL["atol"]
                         + REORDER_TOL["rtol"] * jnp.abs(y_one)))
    print(f"[mesh] (2, 2) mesh vs single device: max|diff| {diff:.3e} "
          f"(rtol {REORDER_TOL['rtol']:g}, atol {REORDER_TOL['atol']:g})")
    check(close, f"mesh result differs from the single-device result "
                 f"by {diff:.3e}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mesh", action="store_true",
                    help="run only the 4-chip fused distributed stepper "
                         "and the single-device result it must match")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == PLATFORM,
          f"no TPU attached (platform {dev.platform!r}); this script "
          f"measures the chip only")
    hw = hardware_for_device(dev)          # an unknown device kind raises
    print(f"device: {dev.platform} {dev.device_kind!r} x{len(devices)} "
          f"(roofline table: {hw.name}); compile cache: {cache_dir}")
    key = jax.random.PRNGKey(args.seed)

    if args.mesh:
        phase_mesh(key, devices)
    else:
        x = jax.random.normal(key, SWEEP_GRID, jnp.float32)
        planned_sweep("a.pallas", "star2d_r2", SWEEP_GRID, SWEEP_STEPS,
                      ["pallas"], x, dev)
        planned_sweep("a.auto", "star2d_r2", SWEEP_GRID, SWEEP_STEPS, None,
                      x, dev)
        del x
        x3 = jax.random.normal(jax.random.fold_in(key, 3), GRID_3D,
                               jnp.float32)
        planned_sweep("a.3d", "box3d_r1", GRID_3D, STEPS_3D, ["pallas"],
                      x3, dev)
        del x3
        x = phase_serve(jax.random.fold_in(key, 4), dev)
        phase_rollout(x, dev)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
