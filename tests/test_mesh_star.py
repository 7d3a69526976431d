"""The paper's 2-D order-2 star on a (2,2) mesh, as the ``star2d_r2_49k``
deployment runs it (49152² over four chips), cut to 1024² on four CPU
devices: 512² per shard, where the planner picks the full size's plan and
the overlap's interior branch is taken.

One child process plans and compiles the problem through ``api.plan`` ->
``api.compile``, runs one 16-step call and reports what the tests below
check: the plan, the gap to a plain reference, the kernels and
``ppermute``s in the call's jaxpr, and the gap with a planted fault.
"""
import json

import pytest

from test_multidevice import run_with_devices

CHILD = """
    import json, re
    import numpy as np, jax, jax.numpy as jnp
    from repro import api
    from repro.core import distributed
    from repro.core.stencil_spec import PAPER_SUITE
    from repro.launch.mesh import make_mesh

    N, STEPS = 1024, 16
    spec = PAPER_SUITE()["star2d_r2"]
    mesh = make_mesh((2, 2), ("gx", "gy"))
    prob = api.StencilProblem(spec, (N, N), dtype="float32",
                              boundary="periodic", steps=STEPS, mesh=mesh,
                              grid_axes=("gx", "gy"))
    plan = api.plan(prob)
    x = jnp.asarray(np.random.default_rng(17).normal(size=(N, N)),
                    jnp.float32)

    def reference(x):
        # the gather sum with periodic rolls, in f32 at HIGHEST; it shares
        # no halo code with the program
        c, r = np.asarray(spec.gather_coeffs), spec.order
        with jax.default_matmul_precision("highest"):
            for _ in range(STEPS):
                acc = jnp.zeros_like(x)
                for (i, j), v in np.ndenumerate(c):
                    if v:
                        acc = acc + jnp.float32(v) * jnp.roll(
                            x, (r - i, r - j), (0, 1))
                x = acc
        return np.asarray(x)

    def gap(run):
        return float(np.abs(np.asarray(run(x)) - ref).max())

    ref = reference(x)
    run = api.compile(plan, mesh=mesh)
    jaxpr = str(jax.make_jaxpr(run.global_fn)(x))
    out = {"plan": [plan.backend, plan.fuse_strategy, plan.fuse_depth,
                    list(plan.block)],
           "ref_max": float(np.abs(ref).max()), "gap": gap(run),
           "kernels": {k: len(re.findall(rf"name={k}\\b", jaxpr))
                       for k in ("stencil_sweep", "stencil_sweep_interior")},
           "ppermutes": jaxpr.count("ppermute")}

    def zero_strips(block, axis, r, mesh_axis, periodic):
        strip = jax.lax.slice_in_dim(block, 0, r, axis=axis)
        return jnp.zeros_like(strip), jnp.zeros_like(strip)

    distributed._exchange_axis = zero_strips
    out["gap_no_exchange"] = gap(api.compile(plan, mesh=mesh))
    print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def mesh_run():
    out = run_with_devices(CHILD, n=4)
    return json.loads(out.split("RESULT", 1)[1])


def test_plan_is_the_full_size_plan(mesh_run):
    """49152² on (2,2) plans the in-kernel Pallas sweep, depth 4, block
    128² (the same plan as the one-chip 16384² sweep)."""
    assert mesh_run["plan"] == ["pallas", "inkernel", 4, [128, 128]]


def test_agrees_with_the_plain_reference(mesh_run):
    """16 steps against the roll-and-sum reference: the same arithmetic
    summed in another order, so within the reordering tolerance."""
    from repro.kernels.ref import REORDER_TOL
    bound = REORDER_TOL["atol"] + REORDER_TOL["rtol"] * mesh_run["ref_max"]
    assert mesh_run["gap"] <= bound, mesh_run


def test_chunk_and_interior_kernels_are_named_apart(mesh_run):
    """Four chunks of depth 4: each runs the sweep over the haloed block
    and again over the interior, under a name of its own."""
    assert mesh_run["kernels"] == {"stencil_sweep": 4,
                                   "stencil_sweep_interior": 4}


def test_one_exchange_per_chunk(mesh_run):
    """Four chunks x two sharded axes x two directions."""
    assert mesh_run["ppermutes"] == 16


def test_exchange_left_as_zeros_fails_the_comparison(mesh_run):
    from repro.kernels.ref import REORDER_TOL
    bound = REORDER_TOL["atol"] + REORDER_TOL["rtol"] * mesh_run["ref_max"]
    assert mesh_run["gap_no_exchange"] > 100 * bound, mesh_run


def test_operator_strategy_interior_kernel_is_named_apart():
    """The operator-fused strategy's interior recompute on the Pallas
    backend runs as ``stencil_step_interior`` too (a one-device mesh is
    enough for the jaxpr)."""
    import re

    import jax
    import jax.numpy as jnp

    from repro.core.distributed import make_fused_distributed_stepper
    from repro.core.stencil_spec import PAPER_SUITE
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("gx", "gy"), devices=jax.devices()[:1])
    run = make_fused_distributed_stepper(
        PAPER_SUITE()["star2d_r2"], mesh, ("gx", "gy"), schedule=(2, 1),
        backend="pallas", block=(32, 32), fuse_strategy="operator")
    jaxpr = str(jax.make_jaxpr(run.global_fn)(
        jax.ShapeDtypeStruct((64, 64), jnp.float32)))
    names = re.findall(r"name=(stencil\w+)", jaxpr)
    assert sorted(names) == ["stencil_step"] * 2 + [
        "stencil_step_interior"] * 2, names
