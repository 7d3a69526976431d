"""Jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, batch axes and differentiation
(custom VJPs built from the adjoint stencil).  ``interpret`` defaults to
:func:`resolve_interpret`'s platform decision: the Pallas interpreter on
the CPU backend, Mosaic on a TPU.

Batch axes (leading axes beyond ``spec.ndim``) are FOLDED into the kernel
as a first-class batch dimension, not vmapped: the whole batch rides one
``pallas_call`` whose per-axis Toeplitz contraction stays a single
``dot_general`` (band operands built once, shared across the batch — the
paper's §4.3 input-vector sharing applied across independent states).
The output matches ``jax.vmap`` of the single-state call to f32 rounding
(the folded contraction may accumulate in another order), but amortizes
one launch and one operand set over the batch.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import coefficient_lines as cl
from repro.core import halo
from repro.core.stencil_spec import StencilSpec, from_gather_coeffs
from repro.kernels import ref as kref
from repro.kernels import stencil_mxu
from repro.kernels import banded_mixer as bm
from repro.kernels.stencil_mxu import resolve_interpret

__all__ = ["stencil_matrixized", "stencil_sweep_matrixized", "banded_mix",
           "pallas_backend_core", "pallas_sweep_core", "resolve_interpret"]


def pallas_backend_core(plan, *, name: str = "stencil_step"):
    """Valid-mode core for the engine/planner backend registry.

    ``plan`` is a :class:`repro.core.engine.StencilPlan`; the returned
    callable is the registry contract (shrinks each spatial axis by
    ``2 * spec.order``) backed by the Pallas MXU kernel, named ``name``.
    """
    return functools.partial(stencil_matrixized, spec=plan.spec,
                             cover=plan.cover, block=plan.block, name=name)


def pallas_sweep_core(plan, steps: int, *, scratch: str = "pingpong",
                      name: str = "stencil_sweep"):
    """Valid-mode T-step core (the registry's ``sweep_builder`` contract).

    Advances ``steps`` applications of ``plan.spec`` per call via the
    in-kernel temporal-blocking kernel — shrinks each spatial axis by
    ``2 * steps * spec.order``, exactly like the ``steps``-fused operator's
    core, so the halo layer and the distributed deep-halo protocol drive it
    unchanged.  ``scratch`` picks the VMEM intermediate policy
    (``"pingpong"`` double buffer | ``"single"`` half the residency);
    ``name`` is the kernel's name in the compiled program.

    The engine hands this core pre-padded arrays and drives it at
    ``boundary="valid"``, so for varying/masked specs the TRUE boundary is
    forwarded as ``aux_boundary`` — the coefficient field must be extended
    into the halo ring the same way the state was.
    """
    return functools.partial(stencil_sweep_matrixized, spec=plan.spec,
                             steps=steps, cover=plan.cover, block=plan.block,
                             scratch=scratch,
                             aux_boundary=plan.boundary, name=name)


def _center_slice(f: np.ndarray, out_sizes) -> np.ndarray:
    """Center a grid-resident scenario field on a smaller output extent.

    Offset ``(field_extent - out_extent) // 2`` per axis — the positional
    convention shared with the gather oracle (:func:`repro.kernels.ref
    .scenario_scale`), which makes valid-mode shrinkage line up
    automatically (after s valid steps the offset is ``s*r``).
    """
    idx = []
    for s, m in zip(f.shape, out_sizes):
        off = (s - m) // 2
        if off < 0:
            raise ValueError(f"scenario field extent {f.shape} smaller than "
                             f"output extent {tuple(out_sizes)}")
        idx.append(slice(off, off + m))
    return f[tuple(idx)]


def _scenario_aux_single(spec: StencilSpec, out_sizes,
                         block) -> tuple[jnp.ndarray, ...]:
    """OUTPUT-aligned aux operands for the single-step kernel.

    Field then mask, each center-sliced to the valid output extent and
    zero-padded on the trailing edge to tile multiples (the padded rows are
    cropped with the output).
    """
    if spec.is_constant_dense:
        return ()
    aux = []
    for f in (spec.coeff_field, spec.domain_mask):
        if f is None:
            continue
        a = _center_slice(np.asarray(f, np.float32), out_sizes)
        pads = [(0, (-s) % b) for s, b in zip(out_sizes, block)]
        if any(p[1] for p in pads):
            a = np.pad(a, pads)
        aux.append(jnp.asarray(a, jnp.float32))
    return tuple(aux)


def _scenario_aux_sweep(spec: StencilSpec, out_sizes, w: int, block,
                        aux_boundary: str) -> tuple[jnp.ndarray, ...]:
    """SLAB-aligned aux operands for the in-kernel sweep.

    Each field is extended centered from its grid extent to the haloed slab
    extent (``out + 2w`` per axis) with the TRUE boundary's pad mode — wrap
    for periodic, zeros otherwise — so every step's sub-slice sees the same
    extension the state does, then zero-padded to tile multiples.
    """
    if spec.is_constant_dense:
        return ()
    target = tuple(s + 2 * w for s in out_sizes)
    mode = halo.pad_mode(aux_boundary) or "constant"
    aux = []
    for f in (spec.coeff_field, spec.domain_mask):
        if f is None:
            continue
        a = np.asarray(f, np.float32)
        # a valid-mode chain whose state already shrank needs the centered
        # SLICE on axes where the grid field exceeds the slab
        a = _center_slice(a, tuple(min(s, t)
                                   for s, t in zip(a.shape, target)))
        pads = []
        for s, t in zip(a.shape, target):
            left = (t - s) // 2
            pads.append((left, t - s - left))
        if any(p != (0, 0) for p in pads):
            a = np.pad(a, pads, mode=mode)
        tile = [(0, (-(t - 2 * w)) % b) for t, b in zip(target, block)]
        if any(p[1] for p in tile):
            a = np.pad(a, tile)
        aux.append(jnp.asarray(a, jnp.float32))
    return tuple(aux)


def _pad_to_multiple(x, block, w, ndim):
    """Zero-pad the ``w``-haloed trailing ``ndim`` spatial axes so the
    valid output tiles evenly (leading batch axes are never padded)."""
    lead = x.ndim - ndim
    pads = [(0, 0)] * lead
    for s, b in zip(x.shape[lead:], block):
        out = s - 2 * w
        pads.append((0, (-out) % b))
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def _feasible_fold(batch: int, residency) -> int:
    """Largest per-instance sub-batch whose VMEM residency fits the budget.

    Folding replaced the old vmap path, which kept ONE state per kernel
    instance — a pinned block that was feasible per state must stay
    executable at any batch, so oversized batches are folded in the
    largest feasible chunks instead of one instance (``residency(c)`` is
    the modelled bytes of a c-state instance).  Never below 1: a single
    state over budget is exactly as (in)feasible as it was pre-batching.
    """
    from repro.core.matrixization import VMEM_BUDGET
    c = batch
    while c > 1 and residency(c) > VMEM_BUDGET:
        c -= 1
    return c


def _fold_call(xb, batch: int, chunk: int, call):
    """Run ``call`` over ``xb`` in lead-axis chunks of ``chunk`` states."""
    if chunk >= batch:
        return call(xb, batch)
    outs = [call(xb[i:i + chunk], min(chunk, batch - i))
            for i in range(0, batch, chunk)]
    return jnp.concatenate(outs, axis=0)


def _default_block(spec: StencilSpec, out_sizes, halo_width: int,
                   batch: int | None = None):
    """The planner's best-ranked MXU-aligned tile for this spatial shape.

    Routing the default through :func:`repro.core.planner.best_block`
    (instead of a hardcoded ``(128, 128)`` / ``(8, 8, 128)`` clamped with a
    raw ``min``) keeps ad-hoc kernel calls on lane/sublane-aligned tiles
    whenever the grid allows one; ``batch`` scales the VMEM feasibility
    bound (a batched instance holds every state's tile).  Deferred import:
    the planner imports the engine, which builds its cores through this
    module.
    """
    from repro.core.planner import best_block
    return best_block(spec, tuple(out_sizes), halo_width=halo_width,
                      batch=batch or 1)


def stencil_matrixized(x: jnp.ndarray, *, spec: StencilSpec,
                       cover: cl.LineCover | None = None,
                       block: tuple[int, ...] | None = None,
                       option: str = "parallel",
                       boundary: str = "valid",
                       interpret: bool | None = None,
                       name: str = "stencil_step") -> jnp.ndarray:
    """Stencil via the Pallas MXU kernel, named ``name``. Batch axes lead.

    ``boundary`` uses the shared halo layer: 'valid' (default) shrinks the
    spatial extent by ``spec.order`` per side; 'zero'/'periodic' pad first
    and preserve shape.
    """
    x = halo.pad_halo(x, spec.order, spec.ndim, boundary)
    lead = x.shape[: x.ndim - spec.ndim]
    out_sizes = tuple(x.shape[x.ndim - spec.ndim + a] - 2 * spec.order
                      for a in range(spec.ndim))
    if cover is None:
        cover = cl.make_cover(spec, option)
    batch = int(np.prod(lead)) if lead else None
    if block is None:
        block = _default_block(spec, out_sizes, spec.order, batch)
    block = tuple(min(b, s) for b, s in zip(block, out_sizes))

    aux = _scenario_aux_single(spec, out_sizes, block)

    if not lead:
        xs = _pad_to_multiple(x, block, spec.order, spec.ndim)
        plan = stencil_mxu.build_kernel_plan(spec, cover, block)
        out = stencil_mxu.stencil_pallas_call(xs, plan, interpret=interpret,
                                              aux=aux, name=name)
        return out[tuple(slice(0, s) for s in out_sizes)]
    if batch == 0:   # empty batch: the old vmap path returned empty too
        return jnp.zeros(lead + out_sizes, x.dtype)

    # fold the leading axes into the kernel batch dimension (band operands
    # shared, per-axis dot count unchanged), chunked so a pinned block
    # stays VMEM-feasible at any batch
    from repro.core import matrixization as mx
    xb = _pad_to_multiple(x.reshape((batch,) + x.shape[len(lead):]),
                          block, spec.order, spec.ndim)

    def call(xc, b):
        plan = stencil_mxu.build_kernel_plan(spec, cover, block, batch=b)
        return stencil_mxu.stencil_pallas_call(xc, plan, interpret=interpret,
                                               aux=aux, name=name)

    chunk = _feasible_fold(batch, lambda c: mx.batched_vmem_bytes(
        block, spec.order, x.dtype.itemsize, c))
    out = _fold_call(xb, batch, chunk, call)
    out = out[(slice(None),) + tuple(slice(0, s) for s in out_sizes)]
    return out.reshape(lead + out_sizes)


def stencil_sweep_matrixized(x: jnp.ndarray, *, spec: StencilSpec,
                             steps: int,
                             cover: cl.LineCover | None = None,
                             block: tuple[int, ...] | None = None,
                             option: str = "parallel",
                             boundary: str = "valid",
                             interpret: bool | None = None,
                             scratch: str = "pingpong",
                             aux_boundary: str | None = None,
                             name: str = "stencil_sweep") -> jnp.ndarray:
    """``steps`` stencil applications in ONE in-kernel temporally-blocked
    pass (paper §6 x §4.3).  Batch axes lead (folded into the kernel's
    batch dimension — one launch, shared per-step band operands).

    Boundary semantics mirror a ``steps``-fused operator: 'valid' shrinks
    the spatial extent by ``steps * spec.order`` per side; 'zero'/'periodic'
    pad the deep halo once and preserve shape ('zero' is the zero-EXTENDED
    evolution — the engine splices per-step-exact strips on top, exactly as
    it does for operator fusion).  ``scratch`` picks the VMEM intermediate
    policy ("pingpong" double buffer | "single" half the residency);
    ``name`` is the kernel's name in the compiled program.

    Varying/masked specs re-read their fields at every in-kernel step; the
    field is extended to the deep-halo slab with ``aux_boundary`` (defaults
    to ``boundary`` — the engine passes the TRUE boundary here because it
    pre-pads and calls at 'valid').  The zero-extended multi-step evolution
    is NOT per-step exact for scenario specs (the strip splice assumes a
    position-independent operator), so 'zero' at ``steps > 1`` is rejected.
    """
    if steps < 1:
        raise ValueError("steps >= 1")
    if aux_boundary is None:
        aux_boundary = boundary
    if steps > 1 and aux_boundary == "zero" and not spec.is_constant_dense:
        raise ValueError(
            "in-kernel sweep with steps > 1 is not exact for varying/"
            "masked specs at boundary='zero' (fall back to depth 1)")
    w = steps * spec.order
    x = halo.pad_halo(x, w, spec.ndim, boundary)
    lead = x.shape[: x.ndim - spec.ndim]
    out_sizes = tuple(x.shape[x.ndim - spec.ndim + a] - 2 * w
                      for a in range(spec.ndim))
    if any(s <= 0 for s in out_sizes):
        raise ValueError(f"input {x.shape} too small for {steps} in-kernel "
                         f"steps of order {spec.order}")
    if cover is None:
        cover = cl.make_cover(spec, option)
    batch = int(np.prod(lead)) if lead else None
    if block is None:
        block = _default_block(spec, out_sizes, w, batch)
    block = tuple(min(b, s) for b, s in zip(block, out_sizes))

    aux = _scenario_aux_sweep(spec, out_sizes, w, block, aux_boundary)

    if not lead:
        xs = _pad_to_multiple(x, block, w, spec.ndim)
        plan = stencil_mxu.build_sweep_kernel_plan(spec, cover, block, steps,
                                                   scratch=scratch)
        out = stencil_mxu.sweep_pallas_call(xs, plan, interpret=interpret,
                                            aux=aux, name=name)
        return out[tuple(slice(0, s) for s in out_sizes)]
    if batch == 0:   # empty batch: the old vmap path returned empty too
        return jnp.zeros(lead + out_sizes, x.dtype)

    from repro.core import matrixization as mx
    xb = _pad_to_multiple(x.reshape((batch,) + x.shape[len(lead):]),
                          block, w, spec.ndim)

    def call(xc, b):
        plan = stencil_mxu.build_sweep_kernel_plan(
            spec, cover, block, steps, batch=b, scratch=scratch)
        return stencil_mxu.sweep_pallas_call(xc, plan, interpret=interpret,
                                             aux=aux, name=name)

    chunk = _feasible_fold(batch, lambda c: mx.inkernel_vmem_bytes(
        block, steps, spec.order, x.dtype.itemsize, cover=cover, batch=c,
        scratch=scratch))
    out = _fold_call(xb, batch, chunk, call)
    out = out[(slice(None),) + tuple(slice(0, s) for s in out_sizes)]
    return out.reshape(lead + out_sizes)


# ---------------------------------------------------------------------------
# Differentiable banded causal mixer (LM integration)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def banded_mix(x: jnp.ndarray, band: jnp.ndarray, block_t: int = 128,
               block_d: int = 128,
               interpret: bool | None = None) -> jnp.ndarray:
    """Differentiable causal banded mix: y[t] = sum_s band[s] x[t-s].

    x: (..., T, D).  band: (W,) shared or (W, D) depthwise.
    """
    return _banded_fwd_impl(x, band, block_t, block_d, interpret)


def _banded_fwd_impl(x, band, block_t, block_d, interpret):
    t_len, d = x.shape[-2], x.shape[-1]
    bt = min(block_t, t_len)
    bd = min(block_d, d)
    pt = (-t_len) % bt
    pd = (-d) % bd

    def single(xs):
        xs_p = jnp.pad(xs, ((0, pt), (0, pd))) if (pt or pd) else xs
        band_p = band if band.ndim == 1 or pd == 0 else jnp.pad(band, ((0, 0), (0, pd)))
        out = bm.banded_mixer_pallas_call(xs_p, band_p, bt, bd, interpret=interpret)
        return out[:t_len, :d]

    fn = single
    for _ in range(x.ndim - 2):
        fn = jax.vmap(fn)
    return fn(x)


def _banded_fwd(x, band, block_t, block_d, interpret):
    return _banded_fwd_impl(x, band, block_t, block_d, interpret), (x, band)


def _banded_bwd(block_t, block_d, interpret, res, g):
    x, band = res
    w = band.shape[0]
    # dx: anti-causal mix with the same band == flip-mix-flip.
    gf = jnp.flip(g, axis=-2)
    dxf = _banded_fwd_impl(gf, band, block_t, block_d, interpret)
    dx = jnp.flip(dxf, axis=-2).astype(x.dtype)
    # dband[s] = sum_t g[t] * x[t-s]  (shared: also sum over channels)
    t_len = x.shape[-2]
    shifted = []
    for s in range(w):
        xs = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(s, 0), (0, 0)])[..., :t_len, :]
        shifted.append(xs)
    xs_stack = jnp.stack(shifted, axis=0)  # (W, ..., T, D)
    if band.ndim == 1:
        dband = jnp.einsum("...td,w...td->w", g.astype(jnp.float32),
                           xs_stack.astype(jnp.float32)).astype(band.dtype)
    else:
        dband = jnp.einsum("...td,w...td->wd", g.astype(jnp.float32),
                           xs_stack.astype(jnp.float32)).astype(band.dtype)
    return dx, dband


banded_mix.defvjp(_banded_fwd, _banded_bwd)


# ---------------------------------------------------------------------------
# Differentiable stencil (learnable-coefficient demo + adjoint tests)
# ---------------------------------------------------------------------------

def stencil_apply_vjp(x: jnp.ndarray, gather_coeffs: jnp.ndarray,
                      interpret: bool | None = None):
    """Valid stencil with gradients w.r.t. both input and coefficients.

    Forward runs the Pallas kernel; the backward pass IS another stencil —
    the adjoint of valid correlation is the zero-padded correlation with the
    scatter coefficients (gather/scatter duality, Eq. 5, used as math not
    just as derivation).
    """

    @jax.custom_vjp
    def apply(x, c):
        spec = from_gather_coeffs(np.asarray(jax.core.concrete_or_error(
            None, c, "coefficients must be concrete for kernel planning")))
        return stencil_matrixized(x, spec=spec, interpret=interpret)

    def fwd(x, c):
        return apply(x, c), (x, c)

    def bwd(res, g):
        x, c = res
        c_np = np.asarray(c)
        spec = from_gather_coeffs(c_np)
        r, nd = spec.order, spec.ndim
        lead = x.ndim - nd
        pad = [(0, 0)] * lead + [(2 * r, 2 * r)] * nd
        adj_spec = from_gather_coeffs(np.asarray(spec.scatter_coeffs))
        dx = kref.stencil_ref(jnp.pad(g, pad), adj_spec).astype(x.dtype)
        # dC[o] = sum_p g[p] * x[p + o]
        grads = []
        for off in np.ndindex(*c_np.shape):
            index = [slice(None)] * lead + [
                slice(o, o + x.shape[lead + a] - 2 * r)
                for a, o in enumerate(off)]
            grads.append(jnp.vdot(g.astype(jnp.float32),
                                  x[tuple(index)].astype(jnp.float32)))
        dc = jnp.stack(grads).reshape(c_np.shape).astype(c.dtype)
        return dx, dc

    apply.defvjp(fwd, bwd)
    return apply(x, gather_coeffs)
