"""In-kernel temporal blocking (fuse_strategy="inkernel"): the multi-step
Pallas sweep kernel with VMEM-resident intermediates, end to end.

The acceptance bar is agreement to f32 rounding (``parity.REORDER_TOL``):
an in-kernel T-step chunk performs the same per-step banded-Toeplitz
contractions as T sequential applications of the same Pallas engine step
(only the tile extents differ, and the extra Toeplitz zeros contribute
+0.0 terms), so the sweep must match ``time_stepper.evolve`` of the
engine's own step_fn to a few ulps.  Not to the bit: the contraction's
operand shapes differ between the two, and XLA:CPU and the MXU choose the
reduction order (and FMA fusion) per shape.  The oracle check (vs the
naive gather reference) guards correctness of the shared arithmetic
separately.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import api
from repro.core import stencil_spec as ss
from repro.core import temporal
from repro.core.engine import StencilEngine
from repro.core.time_stepper import evolve
from repro.kernels.ref import stencil_ref
from parity import REORDER_TOL

SUITE = ss.PAPER_SUITE()
BOUNDARIES = ("valid", "zero", "periodic")
FAST_SPECS = ["box2d_r1", "star2d_r2", "diag2d_r1", "box3d_r1", "star3d_r1"]


def _grid_for(spec, steps, fuse):
    n = max(4 * spec.order * min(fuse, steps) + 4, 6 * spec.order + 6)
    if spec.ndim == 3:
        # keep 3-D interpret-mode grids small, but never below what the
        # total valid-mode shrink 2*r*steps needs to stay feasible
        n = min(n, max(20, 2 * spec.order * steps + 4))
    return (n,) * spec.ndim


def _evolve_ref(eng, x, steps, boundary):
    """Sequential evolution through the engine's OWN per-step fn."""
    if boundary == "valid":
        for _ in range(steps):           # evolve() needs static shapes
            x = eng.step_fn()(x)
        return x
    return evolve(eng.step_fn(), x, steps).state


def _check_inkernel(spec, boundary, steps=3, fuse=2):
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=_grid_for(spec, steps, fuse)), jnp.float32)
    block = (16, 16) if spec.ndim == 2 else (4, 8, 8)
    eng = StencilEngine(spec, backend="pallas", block=block,
                        boundary=boundary)
    out = eng.sweep(x, steps, fuse=fuse, strategy="inkernel")
    seq = _evolve_ref(eng, x, steps, boundary)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(seq), **REORDER_TOL,
        err_msg=f"in-kernel sweep diverged from the per-step sequence: "
                f"{spec.describe()} {boundary} T={fuse}")
    ref = x
    for _ in range(steps):
        ref = stencil_ref(ref, spec, boundary=boundary)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               err_msg=f"{spec.describe()} {boundary}")


@pytest.mark.parametrize("boundary", BOUNDARIES)
@pytest.mark.parametrize("name", FAST_SPECS)
def test_inkernel_sweep_bit_exact_fast(name, boundary):
    _check_inkernel(SUITE[name], boundary)


@pytest.mark.slow
@pytest.mark.parametrize("fuse", [2, 3, 4])
@pytest.mark.parametrize("boundary", BOUNDARIES)
@pytest.mark.parametrize("name", sorted(SUITE))
def test_inkernel_sweep_bit_exact_full_suite(name, boundary, fuse):
    _check_inkernel(SUITE[name], boundary, steps=fuse + 1, fuse=fuse)


def test_inkernel_single_scratch_bit_exact_both_modes():
    """scratch="single"|"pingpong" are the same arithmetic (each step's
    input is a materialized value before write-back), so both must match
    the sequential per-step reference to f32 rounding, the single-buffer
    variant must halve the modelled scratch residency, and the engine's
    core cache must never alias the two compiled variants."""
    spec = SUITE["star2d_r2"]
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.normal(size=(40, 40)), jnp.float32)
    outs = {}
    for scratch in ("pingpong", "single"):
        eng = StencilEngine(spec, backend="pallas", block=(16, 16),
                            boundary="periodic", scratch=scratch)
        outs[scratch] = eng.sweep(x, 4, fuse=2, strategy="inkernel")
        seq = _evolve_ref(eng, x, 4, "periodic")
        np.testing.assert_allclose(np.asarray(outs[scratch]),
                                   np.asarray(seq), **REORDER_TOL,
                                   err_msg=scratch)
        # per-call override keys separately from the engine default
        assert (2, scratch, None) in eng._inkernel_cores
        eng.inkernel_core(2, "single")
        assert (2, "single", None) in eng._inkernel_cores
    np.testing.assert_allclose(np.asarray(outs["pingpong"]),
                               np.asarray(outs["single"]), **REORDER_TOL)
    # modelled residency: one buffer instead of two
    from repro.core import matrixization as mx
    pp = mx.inkernel_vmem_bytes((64, 128), 4, 2)
    single = mx.inkernel_vmem_bytes((64, 128), 4, 2, scratch="single")
    buf = 4 * float(np.prod([b + 2 * 3 * 2 for b in (64, 128)]))
    assert pp - single == pytest.approx(buf)
    with pytest.raises(ValueError):
        StencilEngine(spec, backend="pallas", scratch="bogus")


def test_unknown_chunk_strategy_raises_not_keyerror():
    """A bogus strategy string (e.g. a hand-edited plan) must fail with a
    clear ValueError at the chunk gate, not silently run operator fusion
    or surface a downstream KeyError."""
    import dataclasses
    eng = StencilEngine(SUITE["box2d_r1"], backend="pallas", block=(16, 16),
                        boundary="periodic")
    with pytest.raises(ValueError, match="fuse strategy"):
        eng._apply_chunk(jnp.ones((32, 32), jnp.float32), 2, "bogus")
    prob = api.StencilProblem(SUITE["box2d_r1"], (32, 32),
                              boundary="periodic", steps=4)
    p = api.plan(prob, fuse=2, backends=["pallas"])
    bad = dataclasses.replace(p, fuse_strategy="bogus")
    with pytest.raises(ValueError, match="fuse strategy"):
        api.compile(bad)


def test_inkernel_equals_operator_fusion_values():
    """Both strategies advance the same evolution (allclose — the operator
    strategy rounds differently by construction)."""
    spec = SUITE["star2d_r2"]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    eng = StencilEngine(spec, backend="pallas", block=(16, 16),
                        boundary="periodic")
    ink = eng.sweep(x, 4, fuse=2, strategy="inkernel")
    op = eng.sweep(x, 4, fuse=2, strategy="operator")
    np.testing.assert_allclose(np.asarray(ink), np.asarray(op), atol=1e-4)


def test_inkernel_batched_leading_axes():
    spec = ss.star(2, 1, seed=3)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(2, 20, 20)), jnp.float32)
    eng = StencilEngine(spec, backend="pallas", block=(8, 8),
                        boundary="zero")
    out = eng.sweep(x, 4, fuse=2, strategy="inkernel")
    ref = x
    for _ in range(4):
        ref = stencil_ref(ref, spec, boundary="zero")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


def test_inkernel_requires_sweep_builder():
    eng = StencilEngine(ss.box(2, 1, seed=0), backend="jnp",
                        boundary="periodic")
    assert not eng.supports_inkernel
    x = jnp.ones((16, 16), jnp.float32)
    with pytest.raises(ValueError):
        eng.sweep(x, 4, fuse=2, strategy="inkernel")
    # and "auto" degrades to operator instead of raising
    assert eng._resolve(4, 2, "auto") == (2, "operator")
    with pytest.raises(ValueError):
        eng.sweep(x, 4, fuse=2, strategy="bogus")


def test_engine_auto_strategy_follows_the_roofline_model():
    """The strategy chooser must track where each strategy actually wins:
    a star's per-step cover stays sparse while its fused operator densifies
    to a full box (in-kernel wins), whereas a 2-D box's fused cover is only
    (2Tr+1) lines vs the in-kernel T*(2r+1) — operator fusion stays
    cheaper there.  _resolve_strategy follows temporal.choose_fuse_depth
    in both regimes."""
    star = ss.star(2, 2, seed=1)
    eng_star = StencilEngine(star, backend="pallas", block=(128, 128),
                             boundary="periodic")
    dec = temporal.choose_fuse_depth(star, 3, (128, 128), max_depth=3,
                                     strategies=temporal.FUSE_STRATEGIES)
    assert eng_star._resolve(3, 3, "auto") == (3, dec.candidate(3).strategy)
    assert dec.candidate(3).strategy == "inkernel"
    box = ss.box(2, 2, seed=1)
    eng_box = StencilEngine(box, backend="pallas", block=(16, 16),
                            boundary="periodic")
    assert eng_box._resolve(3, 3, "auto") == (3, "operator")
    # a pinned strategy restricts the DEPTH search too: fuse="auto" with
    # strategy="operator" must pick the operator-optimal depth, not the
    # depth the joint search would choose for inkernel
    d_op, s_op = eng_star._resolve(8, "auto", "operator")
    dec_op = temporal.choose_fuse_depth(star, 8, (128, 128),
                                        strategies=("operator",))
    assert (d_op, s_op) == (dec_op.depth, "operator")
    d_ink, s_ink = eng_star._resolve(8, "auto", "inkernel")
    dec_ink = temporal.choose_fuse_depth(star, 8, (128, 128),
                                         strategies=("inkernel",))
    assert (d_ink, s_ink) == (dec_ink.depth, "inkernel")


def test_inkernel_flops_model_linear_in_t():
    """The cost helpers carry the headline trade: in-kernel flops grow
    ~linearly with T (same per-step cover each step) while operator fusion
    densifies — a fused star loses its star structure entirely, and 3-D
    covers grow as (2Tr+1)^2 lines vs the in-kernel T*(2r+1)^2."""
    from repro.core import coefficient_lines as cl
    from repro.core import matrixization as mx
    from repro.core.engine import choose_cover
    block2, block3 = (128, 128), (64, 64, 64)
    for spec, block in ((ss.star(2, 2, seed=0), block2),
                        (ss.box(3, 2, seed=0), block3),
                        (ss.star(3, 3, seed=0), block3)):
        _, cover = choose_cover(spec, block[0])
        base = mx.mxu_flops(cover, block)
        for t in (2, 4):
            ink = mx.inkernel_mxu_flops(cover, block, t)
            fspec = temporal.fuse_steps(spec, t)
            fused_opts = ("parallel", "minimal") if spec.ndim == 2 \
                else ("parallel",)
            op = min(mx.mxu_flops(cl.make_cover(fspec, o), block)
                     for o in fused_opts)
            assert ink < op, (spec.describe(), t, ink, op)
            assert ink < 2.0 * t * base      # linear-in-T with halo slack
            # traffic identical between the strategies
            assert mx.inkernel_hbm_bytes(block, t, spec.order) == \
                mx.block_hbm_bytes(block, t * spec.order)
    assert temporal.inkernel_traffic_ratio(4) == 0.25
    # VMEM residency grows with the slab depth and gates the planner
    assert mx.inkernel_vmem_bytes(block2, 4, 2) > \
        mx.inkernel_vmem_bytes(block2, 2, 2)


# ---------------------------------------------------------------------------
# Planner integration
# ---------------------------------------------------------------------------

def test_plan_selects_inkernel_with_strictly_lower_cost():
    """Acceptance: on high-order/3D PAPER_SUITE cells the planner picks
    fuse_strategy="inkernel" at depth >= 2 with a strictly lower modelled
    cost than the best operator-fusion candidate."""
    wins = []
    for name in ("star2d_r2", "box3d_r2", "star3d_r3"):
        spec = SUITE[name]
        grid = (256, 256) if spec.ndim == 2 else (64, 64, 64)
        prob = api.StencilProblem(spec, grid, boundary="periodic", steps=16)
        p = api.plan(prob)
        best_op = min(c.t_per_step for c in p.candidates
                      if c.strategy == "operator")
        assert p.fuse_strategy == "inkernel" and p.fuse_depth >= 2, name
        assert p.chosen().t_per_step < best_op, name
        wins.append(name)
    assert wins


def test_plan_strategy_pin_and_round_trip():
    prob = api.StencilProblem(SUITE["box2d_r1"], (64, 64),
                              boundary="periodic", steps=8)
    p_op = api.plan(prob, fuse_strategy="operator")
    assert all(c.strategy == "operator" for c in p_op.candidates)
    p_ink = api.plan(prob, fuse=2, fuse_strategy="inkernel")
    assert p_ink.fuse_strategy == "inkernel"
    assert p_ink.backend == "pallas"  # only backend with a sweep_builder
    assert all(c.strategy == "inkernel" for c in p_ink.candidates
               if c.depth > 1)
    # a pinned-inkernel search still plans when only depth 1 is feasible
    # (a chunk of one step has no strategy), instead of erroring opaquely
    p1 = api.plan(api.StencilProblem(SUITE["box2d_r1"], (64, 64),
                                     boundary="periodic", steps=1),
                  fuse_strategy="inkernel")
    assert p1.fuse_depth == 1 and p1.fuse_strategy == "operator"
    # inkernel rows keep the BASE cover and the plan records it as both
    # option and base_option (the chunk re-applies it per step)
    assert p_ink.option == p_ink.base_option
    q = api.ExecutionPlan.from_json(p_ink.to_json())
    assert q == p_ink and q.fuse_strategy == "inkernel"
    with pytest.raises(ValueError):
        api.plan(prob, fuse_strategy="bogus")
    with pytest.raises(ValueError):  # no backend can execute it
        api.plan(prob, fuse_strategy="inkernel", backends=["jnp"])


def test_plan_inkernel_vmem_pruning():
    """Deep slabs must fit VMEM: a depth that blows the residency budget
    (slab + double-buffered scratch + every step's stacked Toeplitz
    operators) keeps no inkernel candidate at the offending block."""
    from repro.core import coefficient_lines as cl
    from repro.core import matrixization as mx
    from repro.core.planner import _VMEM_BUDGET
    spec = ss.box(2, 3, seed=2)
    prob = api.StencilProblem(spec, (2048, 2048), boundary="periodic",
                              steps=32)
    p = api.plan(prob, max_depth=4)
    for c in p.candidates:
        if c.strategy == "inkernel":
            cover = cl.make_cover(spec, c.option)
            assert mx.inkernel_vmem_bytes(c.block, c.depth, spec.order,
                                          prob.dtype_bytes,
                                          cover=cover) <= _VMEM_BUDGET
    # the operator term matters: it grows the bound beyond the tile model
    cover = cl.make_cover(spec, "parallel")
    assert mx.inkernel_vmem_bytes((512, 256), 4, spec.order, cover=cover) > \
        mx.inkernel_vmem_bytes((512, 256), 4, spec.order)


def test_compile_inkernel_plan_matches_sequential():
    spec = SUITE["box2d_r2"]
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(48, 48)), jnp.float32)
    for boundary in ("periodic", "zero"):
        prob = api.StencilProblem(spec, (48, 48), boundary=boundary, steps=5)
        p = api.plan(prob, fuse=2, fuse_strategy="inkernel")
        assert p.fuse_schedule == (2, 2, 1)
        run = api.compile(p)
        ref = x
        for _ in range(5):
            ref = stencil_ref(ref, spec, boundary=boundary)
        np.testing.assert_allclose(np.asarray(run(x)), np.asarray(ref),
                                   atol=1e-4, err_msg=boundary)
        f = jax.jit(run.fn)
        f(x), f(x)
        assert f._cache_size() == 1, "inkernel compile retraced"


def test_sweep_fn_inkernel_is_jit_safe():
    spec = ss.box(2, 1, seed=0)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(24, 24)), jnp.float32)
    eng = StencilEngine(spec, backend="pallas", block=(8, 8),
                        boundary="periodic")
    fn = eng.sweep_fn(6, fuse=3, grid=(24, 24), strategy="inkernel")
    # the core cache keys (depth, scratch policy) — everything that
    # changes the compiled core
    assert (3, "pingpong", None) in eng._inkernel_cores, \
        "inkernel core was not pre-built"
    f = jax.jit(fn)
    out = f(x)
    f(x), f(x)
    assert f._cache_size() == 1
    ref = x
    for _ in range(6):
        ref = stencil_ref(ref, spec, boundary="periodic")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


# ---------------------------------------------------------------------------
# Calibration integration: per-(backend, strategy) factors
# ---------------------------------------------------------------------------

def test_calibrate_measures_inkernel_factors_separately():
    from repro.launch.calibrate import calibrate, factor_key
    assert factor_key("pallas") == "pallas"
    assert factor_key("pallas", "inkernel") == "pallas:inkernel"
    prob = api.StencilProblem(SUITE["box2d_r1"], (32, 32),
                              boundary="periodic", steps=4)
    rec = calibrate(prob, top_k=2, backends=["pallas"], fuse=2,
                    fuse_strategy="inkernel")
    assert "pallas:inkernel" in rec.compute
    assert all(m.strategy == "inkernel" for m in rec.measurements)
    again = api.CalibrationRecord.from_json(rec.to_json())
    assert again == rec
    # the factors feed back into the matching rows only
    p = api.plan(prob, fuse=2, backends=["pallas"], calibration=rec)
    for c in p.candidates:
        expect = (rec.traffic["pallas:inkernel"]
                  if c.strategy == "inkernel" else 1.0)
        uncal = api.candidate_cost(prob, c.depth, c.option, c.backend,
                                   block=c.block, strategy=c.strategy)
        assert c.t_traffic == pytest.approx(uncal.t_traffic * expect)
