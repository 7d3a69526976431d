"""Distributed 2-D heat equation through the unified plan/compile API.

The problem declares the mesh; the planner picks cover x backend x fuse
depth by roofline model and records every decision; compile() emits the
fused sharded stepper — ONE ``T*r``-deep halo exchange per fused chunk
(collective-permutes counted below), interior update overlapped with the
wire time (DESIGN.md §Planner).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/pde_halo_exchange.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro import api
from repro.core.engine import StencilEngine
from repro.launch.mesh import make_mesh


def main():
    n_dev = len(jax.devices())
    gx = max(d for d in (1, 2, 4, 8) if n_dev % d == 0 and d * 1 <= n_dev)
    gy = n_dev // gx
    mesh = make_mesh((gx, gy), ("gx", "gy"))
    print(f"devices={n_dev} mesh=({gx},{gy})")

    # 2D9P heat-like stencil (normalized coefficients -> diffusion)
    spec = api.box(2, 1, seed=0)
    steps = 50
    problem = api.StencilProblem(spec, grid=(256, 256), boundary="periodic",
                                 steps=steps, mesh=mesh,
                                 grid_axes=("gx", "gy"))
    # jnp backend pin: on the CPU the Pallas kernels run in the (slow)
    # interpreter
    plan = api.plan(problem, backends=["jnp"], max_depth=5)
    print(plan.explain())

    step = api.compile(plan, mesh=mesh)
    field = jnp.zeros((256, 256), jnp.float32).at[128, 128].set(1000.0)
    out = step(field)
    print(f"after {steps} steps (schedule {plan.fuse_schedule}): "
          f"mass={float(out.sum()):9.3f} peak={float(out.max()):.5f}")

    # verify against the single-device engine
    eng = StencilEngine(spec, boundary="periodic")
    ref = field
    for _ in range(steps):
        ref = eng(ref)
    err = float(jnp.abs(out - ref).max())
    print(f"max |distributed fused - single-device sequential|: {err:.2e}")
    assert err < 1e-4

    # the collective schedule proof: one T*r-deep exchange per fused chunk
    n_chunks = len(plan.fuse_schedule)
    n_pp = str(jax.make_jaxpr(step.global_fn)(field)).count("ppermute")
    print(f"ppermutes in jaxpr: {n_pp} "
          f"(= {n_chunks} chunks x 2 mesh axes x 2 directions)")
    assert n_pp == n_chunks * 2 * 2

    txt = jax.jit(step.fn).lower(
        jax.ShapeDtypeStruct(field.shape, field.dtype)).compile().as_text()
    print(f"collective-permutes in compiled HLO: "
          f"{txt.count('collective-permute')}")

    # the modelled story the planner told
    ch = plan.chosen()
    print(f"chosen depth={plan.fuse_depth} cover={plan.option} "
          f"backend={plan.backend}: modelled "
          f"{ch.t_per_step * 1e9:.1f} ns/step on {plan.hw['name']}, "
          f"halo traffic {ch.ici_bytes / 1e3:.1f} kB/chunk over ICI")


if __name__ == "__main__":
    main()
