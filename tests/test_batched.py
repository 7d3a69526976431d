"""Batched sweep execution: the batch axis folded into the MXU
contractions, end to end.

The acceptance bar is agreement to f32 rounding (``parity.REORDER_TOL``)
with a ``jax.vmap`` of the single-state ``sweep_fn``: folding B states
into one kernel instance issues the SAME per-state banded-Toeplitz
contractions (the batch rides the slab operand of each ``dot_general``;
the band operand is shared), but the folded contraction has other operand
shapes, and XLA:CPU and the MXU pick the reduction order per shape — so
the batched output matches the vmapped per-state reference to a few ulps,
not to the bit.  The structural claim is checked on the jaxpr: the per-axis
``dot_general`` count does NOT grow with B.  The cost-model claim —
batching fills the MXU rows a small grid leaves idle and amortizes the
per-chunk dispatch overhead, so modelled per-STATE cost falls with B —
is asserted over the PAPER_SUITE (the BENCH_serve.json acceptance
criterion, 7/13 cells minimum).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parity import REORDER_TOL, assert_sweep_parity, parity_grid
from repro import api
from repro.core import matrixization as mx
from repro.core import stencil_spec as ss
from repro.core.engine import StencilEngine
from repro.kernels import ops

SUITE = ss.PAPER_SUITE()
FAST_SPECS = ["box2d_r1", "star2d_r2", "diag2d_r1", "box3d_r1", "star3d_r1"]
BATCHES = [1, 3, 8]
STRATEGIES = ("operator", "inkernel")


def _engine_for(spec, boundary):
    block = (16, 16) if spec.ndim == 2 else (4, 8, 8)
    return StencilEngine(spec, backend="pallas", block=block,
                         boundary=boundary)


def _grid_for(spec, steps=4):
    return parity_grid(spec, steps)


def _check_batched_parity(spec, boundary, batch, strategy, steps=4, fuse=2):
    # the shared harness does both bars: REORDER_TOL vs jax.vmap of the
    # same sweep closure, and atol=1e-4 vs the iterated gather oracle
    assert_sweep_parity(spec, boundary, strategy, fuse, batch, steps=steps,
                        seed=batch * 10 + steps)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("batch", BATCHES)
@pytest.mark.parametrize("name", FAST_SPECS)
def test_batched_sweep_bit_exact_vs_vmap_fast(name, batch, strategy):
    _check_batched_parity(SUITE[name], "periodic", batch, strategy)


@pytest.mark.slow
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("boundary", ("valid", "zero", "periodic"))
@pytest.mark.parametrize("name", sorted(SUITE))
def test_batched_sweep_bit_exact_full_suite(name, boundary, strategy):
    for batch in BATCHES:
        _check_batched_parity(SUITE[name], boundary, batch, strategy)


def test_batched_zero_boundary_strips():
    """The Dirichlet-0 strip splice must stay per-step-exact per state."""
    for strategy in STRATEGIES:
        _check_batched_parity(SUITE["star2d_r2"], "zero", 3, strategy)


# ---------------------------------------------------------------------------
# Structure: bands shared, batch folded — dots do not grow with B
# ---------------------------------------------------------------------------

def _dot_count(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("dot_general")


@pytest.mark.parametrize("name", ["box2d_r1", "star2d_r2", "box3d_r1"])
def test_per_axis_dot_count_independent_of_batch(name):
    spec = SUITE[name]
    grid = _grid_for(spec)

    def single_step(b):
        x = jnp.zeros((b,) + grid, jnp.float32)
        return _dot_count(lambda a: ops.stencil_matrixized(
            a, spec=spec, boundary="periodic"), x)

    def sweep(b):
        x = jnp.zeros((b,) + grid, jnp.float32)
        return _dot_count(lambda a: ops.stencil_sweep_matrixized(
            a, spec=spec, steps=3, boundary="periodic"), x)

    assert single_step(1) == single_step(8) > 0
    assert sweep(1) == sweep(8) > 0
    # vmapping the same call instead would NOT change the count either
    # (jax batches dots), so also pin the absolute structure: one dot per
    # axis group per step (the wrappers' default is the parallel cover)
    from repro.core import coefficient_lines as cl
    cover = cl.make_cover(spec, "parallel")
    axes = {line.axis for line in cover.lines
            if not line.is_diagonal and line.nnz > 1}
    assert single_step(8) == len(axes)
    assert sweep(8) == 3 * len(axes)


def test_empty_batch_returns_empty_like_the_old_vmap_path():
    spec = SUITE["box2d_r1"]
    x = jnp.zeros((0, 12, 12), jnp.float32)
    out = ops.stencil_matrixized(x, spec=spec, boundary="periodic")
    assert out.shape == (0, 12, 12) and out.dtype == x.dtype
    out = ops.stencil_sweep_matrixized(x, spec=spec, steps=2,
                                       boundary="periodic")
    assert out.shape == (0, 12, 12)


def test_oversized_batch_folds_in_vmem_feasible_chunks():
    """A pinned block that is VMEM-feasible per state must stay
    executable (and correct) at ANY batch: the wrappers split the fold
    into feasible sub-batches instead of one oversized instance."""
    from repro.kernels.ops import _feasible_fold
    spec = SUITE["box2d_r1"]
    rng = np.random.default_rng(17)
    # (256, 256) f32 tile ~0.5 MB haloed/state: 64 states blow the 8 MB
    # budget in one instance
    x = jnp.asarray(rng.normal(size=(64, 256, 256)), jnp.float32)
    chunk = _feasible_fold(64, lambda c: mx.batched_vmem_bytes(
        (256, 256), spec.order, 4, c))
    assert 1 <= chunk < 64
    out = ops.stencil_matrixized(x, spec=spec, block=(256, 256),
                                 boundary="periodic")
    fn = lambda a: ops.stencil_matrixized(a, spec=spec, block=(256, 256),
                                          boundary="periodic")
    np.testing.assert_allclose(np.asarray(out[:2]),
                               np.asarray(jax.vmap(fn)(x[:2])), **REORDER_TOL)
    # a single over-budget state stays exactly as feasible as pre-batching
    assert _feasible_fold(4, lambda c: float("inf")) == 1


def test_batched_single_step_matches_vmap_bit_exact():
    spec = SUITE["star2d_r2"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 40, 40)), jnp.float32)
    fn = lambda a: ops.stencil_matrixized(a, spec=spec, boundary="periodic")
    np.testing.assert_allclose(np.asarray(fn(x)),
                               np.asarray(jax.vmap(fn)(x)), **REORDER_TOL)


# ---------------------------------------------------------------------------
# dtype: bf16 states through the batched f32-accumulating kernel
# ---------------------------------------------------------------------------

def test_batched_bf16_vs_f32_tolerance():
    """bf16 inputs ride the same batched kernel (f32 accumulation inside),
    so the batched bf16 sweep must track the f32 one to bf16 resolution
    and its own vmapped reference to one bf16 rounding (an f32 ulp of
    reordering can flip the final bf16 rounding)."""
    spec = SUITE["box2d_r1"]
    rng = np.random.default_rng(11)
    xf = jnp.asarray(rng.normal(size=(4, 40, 40)), jnp.float32)
    xb = xf.astype(jnp.bfloat16)
    eng = _engine_for(spec, "periodic")
    fn = eng.sweep_fn(4, fuse=2, grid=(40, 40))
    out_b, out_f = fn(xb), fn(xf)
    assert out_b.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_b, np.float32),
                               np.asarray(jax.vmap(fn)(xb), np.float32),
                               rtol=2 ** -7, atol=2 ** -7)
    # bf16 has ~3 decimal digits; the evolution is contractive (weights
    # sum to 1) so absolute tolerance at bf16 epsilon scale is the bar
    np.testing.assert_allclose(np.asarray(out_b, np.float32),
                               np.asarray(out_f), atol=0.05)


# ---------------------------------------------------------------------------
# Planner integration: batch is a first-class, planner-visible dimension
# ---------------------------------------------------------------------------

def test_problem_batch_validation_and_round_trip():
    with pytest.raises(ValueError):
        api.StencilProblem(SUITE["box2d_r1"], (32, 32), batch=0)
    prob = api.StencilProblem(SUITE["box2d_r1"], (32, 32), steps=4, batch=8)
    assert prob.to_dict()["batch"] == 8
    p = api.plan(prob, backends=["jnp"])
    assert p.batch == 8
    assert all(c.batch == 8 for c in p.candidates)
    q = api.ExecutionPlan.from_json(p.to_json())
    assert q == p and q.batch == 8
    assert "batch" in p.explain()


def test_modelled_per_state_cost_falls_with_batch():
    """Acceptance: per-state modelled cost at B=8 strictly below B=1 on
    >= 7 of 13 PAPER_SUITE cells (t_per_step is already per state)."""
    wins = []
    for name in sorted(SUITE):
        spec = SUITE[name]
        grid = (256, 256) if spec.ndim == 2 else (64, 64, 64)
        per_state = {}
        for b in (1, 8):
            prob = api.StencilProblem(spec, grid, boundary="periodic",
                                      steps=16, batch=b)
            per_state[b] = api.plan(prob).chosen().t_per_step
        if per_state[8] < per_state[1]:
            wins.append(name)
    assert len(wins) >= 7, f"only {len(wins)}/13 cells improved: {wins}"


def test_batched_cost_helpers_reduce_to_legacy_at_batch_one():
    from repro.core import coefficient_lines as cl
    from repro.core.engine import choose_cover
    spec = SUITE["star2d_r2"]
    block = (64, 128)
    _, cover = choose_cover(spec, block[0])
    assert mx.batched_mxu_flops(cover, block, 1) == mx.mxu_flops(cover, block)
    assert mx.batched_inkernel_mxu_flops(cover, block, 3, 1) == \
        mx.inkernel_mxu_flops(cover, block, 3)
    assert mx.batched_hbm_bytes(block, 2, 4, 1) == mx.block_hbm_bytes(
        block, 2, 4)
    # per-state flops strictly improve (the M-fill) while traffic is linear
    assert mx.batched_mxu_flops(cover, block, 8) < \
        8 * mx.batched_mxu_flops(cover, block, 1)
    assert mx.batched_hbm_bytes(block, 2, 4, 8) == \
        8 * mx.batched_hbm_bytes(block, 2, 4, 1)
    # batched VMEM residency gates the block search
    assert mx.batched_vmem_bytes(block, 2, 4, 8) == \
        8 * mx.batched_vmem_bytes(block, 2, 4, 1)


def test_compile_batched_plan_matches_vmapped_compile():
    spec = SUITE["box2d_r2"]
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(3, 40, 40)), jnp.float32)
    for boundary in ("periodic", "zero"):
        prob1 = api.StencilProblem(spec, (40, 40), boundary=boundary,
                                   steps=5)
        prob3 = api.StencilProblem(spec, (40, 40), boundary=boundary,
                                   steps=5, batch=3)
        run1 = api.compile(api.plan(prob1, fuse=2, backends=["pallas"],
                                    block=(16, 16)))
        run3 = api.compile(api.plan(prob3, fuse=2, backends=["pallas"],
                                    block=(16, 16)))
        np.testing.assert_allclose(np.asarray(run3(x)),
                                   np.asarray(jax.vmap(run1.fn)(x)),
                                   **REORDER_TOL, err_msg=boundary)
        # a batched plan rejects un-batched input
        with pytest.raises(ValueError):
            run3(x[0])
    f = jax.jit(run3.fn)
    f(x), f(x)
    assert f._cache_size() == 1, "batched compile retraced"


def test_batched_inkernel_vmem_gate_prunes_by_batch():
    """A batch that blows the in-kernel VMEM residency keeps no inkernel
    candidate at the offending (block, depth)."""
    from repro.core import coefficient_lines as cl
    from repro.core.planner import _VMEM_BUDGET
    spec = SUITE["box2d_r3"]
    prob = api.StencilProblem(spec, (2048, 2048), boundary="periodic",
                              steps=32, batch=8)
    p = api.plan(prob, max_depth=4)
    for c in p.candidates:
        if c.strategy == "inkernel":
            cover = cl.make_cover(spec, c.option)
            assert mx.inkernel_vmem_bytes(c.block, c.depth, spec.order,
                                          prob.dtype_bytes, cover=cover,
                                          batch=8) <= _VMEM_BUDGET
