"""Cross-strategy parity harness (the ISSUE-8 gate).

One reusable entry point — :func:`assert_sweep_parity` — checks a fused
engine sweep against the gather-mode oracle (``time_stepper
.reference_evolve``) for ANY spec kind (constant / varying-coefficient /
masked), any boundary, either fuse strategy, and an optional folded batch
axis.  The bars are the repo-wide ones:

* ``atol=1e-4`` against the iterated gather oracle (XLA:CPU contracts the
  banded dots with FMA, so exact equality across ``steps`` applications is
  not the right bar — see DESIGN.md §Numerics);
* agreement to f32 rounding (:data:`REORDER_TOL`) of a batched sweep
  against ``jax.vmap`` of the same closure (folding states must not change
  the per-state arithmetic, but may change its accumulation order);
* an ILLEGAL explicit (strategy, depth) pin — e.g. operator fusion at
  depth > 1 over a varying-coefficient spec — must raise ``ValueError``
  from the engine, never silently apply the constant-coefficient fused
  operator.  The harness asserts the raise, so every parity sweep doubles
  as the fusion-legality regression.

Seeded generators (``draw_base_spec`` / ``with_scenario`` /
``draw_scenario_spec``) plug into ``prop.prop_cases`` for randomized
tier-1 coverage; ``tests/test_parity.py`` drives them and
``tests/test_batched.py`` routes its batched parity loops through the same
entry point.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import stencil_spec as ss
from repro.core import temporal
from repro.core.engine import StencilEngine
from repro.core.time_stepper import reference_evolve
from repro.kernels.ref import REORDER_TOL

__all__ = ["SCENARIOS", "REORDER_TOL", "parity_grid", "draw_base_spec",
           "with_scenario", "draw_scenario_spec", "assert_sweep_parity"]

#: Coefficient/domain scenarios a spec can carry (plan dimensions, ISSUE 8).
SCENARIOS = ("constant", "varying", "masked", "varying+masked")


def parity_grid(spec, steps: int = 4) -> tuple[int, ...]:
    """Smallest grid the full parity matrix runs on: 'valid' shrinks the
    state 2r per step, so high-order 3-D cells need headroom."""
    n = 40 if spec.ndim == 2 else max(20, 2 * spec.order * steps + 4)
    return (n,) * spec.ndim


def draw_base_spec(draw):
    """Seeded constant-coefficient spec: 2-D/3-D star or box, r in {1, 2}
    (2-D) or r=1 (3-D keeps interpret-mode runtime in budget)."""
    ndim = draw.choice((2, 3))
    order = draw.choice((1, 2)) if ndim == 2 else 1
    factory = ss.star if draw.bool() else ss.box
    return factory(ndim, order, seed=draw.int(0, 9999))


def with_scenario(spec, grid, kind: str, seed: int = 0):
    """Attach a seeded coefficient field and/or domain mask on ``grid``."""
    if kind not in SCENARIOS:
        raise ValueError(f"unknown scenario {kind!r}; choose {SCENARIOS}")
    if kind == "constant":
        return spec
    field = (ss.random_coeff_field(grid, seed=seed)
             if "varying" in kind else None)
    mask = (ss.random_domain_mask(grid, seed=seed + 1)
            if "mask" in kind else None)
    if field is not None:
        return spec.with_field(field, domain_mask=mask)
    return spec.with_mask(mask)


def draw_scenario_spec(draw, steps: int = 4):
    """Seeded (spec, grid) pair covering all four scenario kinds."""
    base = draw_base_spec(draw)
    grid = parity_grid(base, steps)
    kind = draw.choice(SCENARIOS)
    return with_scenario(base, grid, kind, seed=draw.int(0, 9999)), grid


def assert_sweep_parity(spec, boundary: str, strategy: str = "auto",
                        depth="auto", batch: int = 0, *, steps: int = 4,
                        grid: tuple[int, ...] | None = None, seed: int = 0,
                        backend: str = "pallas",
                        block: tuple[int, ...] | None = None,
                        atol: float = 1e-4):
    """Fused-sweep parity for one (spec, boundary, strategy, depth, batch).

    ``batch=0`` runs a single un-batched state; ``batch>=1`` folds that
    many states and additionally requires agreement within
    :data:`REORDER_TOL` with ``jax.vmap`` of the same sweep closure.  ``depth`` is the fuse pin
    (int) or ``"auto"``.  If the explicit (strategy, depth) pin is illegal
    for the spec/boundary (``temporal.fusion_legal``), the engine MUST
    refuse with ``ValueError`` — the harness asserts that and returns
    ``None``; otherwise it returns the sweep output after the checks pass.
    """
    if grid is None:
        grid = parity_grid(spec, steps)
    grid = tuple(grid)
    if block is None:
        block = (16, 16) if spec.ndim == 2 else (4, 8, 8)
    eng = StencilEngine(spec, backend=backend, block=block,
                        boundary=boundary)

    label = (f"{spec.describe()} boundary={boundary} strategy={strategy} "
             f"depth={depth} batch={batch} steps={steps}")
    pinned = strategy != "auto" and isinstance(depth, int)
    if pinned and not temporal.fusion_legal(spec, boundary, strategy, depth):
        try:
            fn = eng.sweep_fn(steps, fuse=depth, grid=grid,
                              strategy=strategy)
            fn(jnp.zeros(grid, jnp.float32))
        except ValueError:
            return None
        raise AssertionError(
            f"illegal fused pin silently accepted (would apply the "
            f"constant-coefficient operator): {label}")

    rng = np.random.default_rng(seed)
    shape = ((batch,) + grid) if batch else grid
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    fn = eng.sweep_fn(steps, fuse=depth, grid=grid, strategy=strategy)
    out = fn(x)
    ref = reference_evolve(spec, x, steps, boundary)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=atol,
        err_msg=f"sweep diverged from gather oracle: {label}")
    if batch:
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(jax.vmap(fn)(x)), **REORDER_TOL,
            err_msg=f"batched sweep diverged from vmap: {label}")
    return out
