"""Stencil matrixization: outer-product sums as banded-Toeplitz matmuls.

Paper Eq. 12 expresses one coefficient line's contribution to an n-row
output block as ``2r+n`` vector outer products.  On TPU the accumulated sum
of those rank-1 updates *is* a matmul:

    sum_i  (slice_i of C° column) ⊗ A[i, :]   ==   T @ A_slab

where ``T`` is the ``n x (n+2r)`` banded Toeplitz operator carrying the
line's taps on its diagonals and ``A_slab`` the haloed input window.  This
module builds those operators and evaluates stencils with them, in any
dimension, for any line cover from :mod:`repro.core.coefficient_lines`.

Gather/scatter bookkeeping: a scatter line (slice of Cs) along axis ``a``
with fixed scatter offsets ``f_d`` equals the gather band
``line.coeffs[::-1]`` applied at gather offsets ``(E-1) - f_d`` on the other
axes (Cs = Cg reversed on every axis, Eq. 5).

Beyond-paper (TPU-only) path: SVD-separable factorization
``Cg = sum_p sigma_p u_p v_p^T`` evaluates a 2-D stencil as
``sum_p  T_{u_p} @ A @ T_{v_p}^T`` — ``2*rank`` slab matmuls, impossible on
SME (no right-multiply against an accumulator tile).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.coefficient_lines import CoefficientLine, LineCover
from repro.core.stencil_spec import StencilSpec

__all__ = [
    "contract_precision",
    "toeplitz_band",
    "toeplitz_band_np",
    "banded_operator",
    "line_to_gather_band",
    "matrixized_apply",
    "scenario_scale",
    "aux_hbm_bytes",
    "n_aux_operands",
    "active_block_fraction",
    "separable_factors",
    "separable_apply",
    "matmul_count",
    "mxu_flops",
    "separable_mxu_flops",
    "inkernel_mxu_flops",
    "inkernel_hbm_bytes",
    "inkernel_vmem_bytes",
    "block_hbm_bytes",
    "batched_mxu_flops",
    "batched_inkernel_mxu_flops",
    "batched_hbm_bytes",
    "batched_vmem_bytes",
    "MXU_ROWS",
    "VMEM_BYTES",
    "VMEM_BUDGET",
    "SCRATCH_MODES",
    "check_scratch",
]

#: VMEM scratch policies of the in-kernel sweep: "pingpong" double-buffers
#: the intermediate slab (reads never target the buffer being written even
#: if Mosaic pipelines the steps); "single" reuses ONE buffer — each
#: step's input is fully materialized as a value before the write-back, so
#: one suffices at half the scratch residency.  Defined here (the lowest
#: layer that models the residency) and re-exported by ``temporal`` next
#: to the other temporal-blocking policy constants.
SCRATCH_MODES = ("pingpong", "single")


def check_scratch(scratch: str) -> str:
    if scratch not in SCRATCH_MODES:
        raise ValueError(f"unknown scratch mode {scratch!r}; choose from "
                         f"{SCRATCH_MODES}")
    return scratch

# v5e/v5p VMEM per core, and the fraction of it a kernel instance's tile
# residency may claim (the rest is Toeplitz operators + slack).  Shared by
# the planner's block search / inkernel pruning AND the engine-level
# temporal chooser, so the two layers can never disagree on feasibility.
VMEM_BYTES = 16 * 2 ** 20
VMEM_BUDGET = 0.5 * VMEM_BYTES


def toeplitz_band_np(band: np.ndarray, n_out: int) -> np.ndarray:
    """Numpy-side banded Toeplitz operator (n_out, n_out + len(band) - 1).

    Kernel PLANNING must stay in numpy: it runs inside jit traces (the
    Pallas call site builds its plan per input shape), where a jnp
    intermediate would be a tracer and poison any ``np.asarray`` on it.
    """
    band = np.asarray(band)
    w = band.shape[0]
    t = np.zeros((n_out, n_out + w - 1), dtype=np.float64)
    rows = np.arange(n_out)
    for s in range(w):
        t[rows, rows + s] = band[s]
    return t


def toeplitz_band(band: np.ndarray, n_out: int, dtype=jnp.float32) -> jnp.ndarray:
    """Banded Toeplitz operator T of shape (n_out, n_out + len(band) - 1).

    ``T[k, k+s] = band[s]`` — contracting T against a haloed slab applies
    the 1-D gather stencil ``band`` along the contracted axis.
    """
    return jnp.asarray(toeplitz_band_np(band, n_out), dtype=dtype)


def banded_operator(band: np.ndarray, n_out: int,
                    field_line: np.ndarray | None = None) -> np.ndarray:
    """Per-axis banded operand: Toeplitz for constant coefficients, the
    ``spdiags``-shaped banded matrix ``diag(field_line) @ T`` for a
    varying-coefficient line (each output row carries its own point's
    coefficient scale).

    With ``field_line=None`` this IS :func:`toeplitz_band_np` bit-exactly —
    the constant case reduces to the shared band.  The runtime paths never
    materialize this matrix: they factor it as the shared Toeplitz
    contraction followed by an elementwise f32 row scale
    (:func:`scenario_scale`), preserving one ``dot_general`` per axis;
    this constructor is the semantic definition those paths are tested
    against (DESIGN.md §Scenarios).
    """
    t = toeplitz_band_np(band, n_out)
    if field_line is None:
        return t
    a = np.asarray(field_line, dtype=np.float64)
    if a.shape != (n_out,):
        raise ValueError(f"field_line shape {a.shape} != ({n_out},)")
    return a[:, None] * t


def scenario_scale(acc: jnp.ndarray, spec: StencilSpec,
                   accum_dtype=jnp.float32) -> jnp.ndarray:
    """Scale a valid-mode accumulator by a spec's scenario fields.

    ``y = M * (a * acc)`` — the coefficient field and the domain mask are
    CENTER-sliced to the accumulator's spatial extent (offset
    ``(field_extent - out_extent) // 2`` per axis).  The centered slice is
    the whole positional convention: under 'valid' evolution step ``s``'s
    output sits ``s*r`` in from the original grid edge, which is exactly
    the centered offset, and for shape-preserving boundaries the slice is
    the identity.  Applied AFTER the banded-Toeplitz accumulation in f32
    (the ``diag(a) @ T`` factorization), identically in every execution
    path and the gather oracle, so cross-path parity stays bit-exact.
    No-op for constant unmasked specs.
    """
    if spec.is_constant_dense:
        return acc
    ndim = spec.ndim
    out_spatial = acc.shape[acc.ndim - ndim:]

    def center(field):
        f = np.asarray(field)
        idx = []
        for a, m in enumerate(out_spatial):
            off = (f.shape[a] - m) // 2
            if off < 0:
                raise ValueError(
                    f"scenario field extent {f.shape} smaller than output "
                    f"extent {out_spatial}")
            idx.append(slice(off, off + m))
        return f[tuple(idx)]

    if spec.is_varying:
        acc = acc * jnp.asarray(center(spec.coeff_field), accum_dtype)
    if spec.is_masked:
        acc = acc * jnp.asarray(center(spec.domain_mask), accum_dtype)
    return acc


def contract_precision(state_dtype):
    """Matmul precision of the banded contractions for a ``state_dtype`` state.

    f32 states contract at HIGHEST: a TPU runs an f32 ``dot_general`` at
    DEFAULT precision as a single bf16 pass (TPU v5e, star2d_r2 on 4096²
    over 16 steps: max error 8.4e-3 against the f32 gather oracle, 1.5e-7
    at HIGHEST).  Other state dtypes keep the default.
    """
    if jnp.dtype(state_dtype) == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return None


def line_to_gather_band(line: CoefficientLine, spec: StencilSpec):
    """(gather band, gather fixed offsets) for an axis-parallel scatter line."""
    if line.is_diagonal:
        raise ValueError("diagonal lines use skewed evaluation, not bands")
    e = spec.extent
    band = np.asarray(line.coeffs)[::-1]
    fixed = {a: (e - 1) - v for a, v in line.fixed}
    return band, fixed


def _valid_shape(x_shape, ndim, r):
    lead = x_shape[: len(x_shape) - ndim]
    spatial = tuple(s - 2 * r for s in x_shape[len(x_shape) - ndim:])
    if any(s <= 0 for s in spatial):
        raise ValueError(f"input {x_shape} too small for order {r}")
    return lead, spatial


def _line_contribution(x: jnp.ndarray, spec: StencilSpec, line: CoefficientLine,
                       dtype) -> jnp.ndarray:
    """One line's contribution to the valid-mode output, as a matmul."""
    ndim = spec.ndim
    r = spec.order
    lead_n = x.ndim - ndim
    band, fixed = line_to_gather_band(line, spec)
    axis = line.axis + lead_n

    # Slice the slab: full halo along the line axis, pinned offset elsewhere.
    index = [slice(None)] * x.ndim
    for a_sp, off in fixed.items():
        a = a_sp + lead_n
        index[a] = slice(off, off + x.shape[a] - 2 * r)
    slab = x[tuple(index)]

    n_out = x.shape[axis] - 2 * r
    t = toeplitz_band(band, n_out, dtype=dtype)
    # Contract T's halo axis against the slab's line axis.
    out = jnp.tensordot(t, slab, axes=((1,), (axis,)),
                        precision=contract_precision(x.dtype))
    # tensordot puts the contracted result axis first; restore position.
    return jnp.moveaxis(out, 0, axis)


def _diagonal_contribution(x: jnp.ndarray, spec: StencilSpec,
                           line: CoefficientLine, dtype) -> jnp.ndarray:
    """Diagonal line: per-tap shifted accumulation (Eq. 16 family).

    Each diagonal tap shifts every participating axis simultaneously; on TPU
    this is cheapest as shifted-slab adds (the skew would otherwise force a
    gather).  Kept for cover completeness.
    """
    ndim = spec.ndim
    r = spec.order
    e = spec.extent
    lead_n = x.ndim - ndim
    _, spatial = _valid_shape(x.shape, ndim, r)
    out = jnp.zeros(x.shape[:lead_n] + spatial, dtype=dtype)
    for o, c in enumerate(np.asarray(line.coeffs)):
        if c == 0.0:
            continue
        index = [slice(None)] * x.ndim
        # scatter index o along each (axis, dir); convert to gather offset.
        offs = {a: (o if d > 0 else e - 1 - o) for a, d in line.axis}
        for a, v in line.fixed:
            offs[a] = v
        for a_sp in range(ndim):
            g = (e - 1) - offs[a_sp]  # gather offset
            a = a_sp + lead_n
            index[a] = slice(g, g + x.shape[a] - 2 * r)
        out = out + jnp.asarray(c, dtype) * x[tuple(index)].astype(dtype)
    return out


def matrixized_apply(x: jnp.ndarray, spec: StencilSpec, cover: LineCover,
                     accum_dtype=jnp.float32) -> jnp.ndarray:
    """Valid-mode stencil via the cover's banded-Toeplitz matmuls.

    Leading axes of ``x`` beyond ``spec.ndim`` are batch axes.
    """
    lead, spatial = _valid_shape(x.shape, spec.ndim, spec.order)
    out = jnp.zeros(lead + spatial, dtype=accum_dtype)
    for line in cover.lines:
        if line.is_diagonal:
            out = out + _diagonal_contribution(x, spec, line, accum_dtype)
        else:
            out = out + _line_contribution(x, spec, line, accum_dtype)
    out = scenario_scale(out, spec, accum_dtype)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Beyond-paper: separable (SVD) factorization, 2-D
# ---------------------------------------------------------------------------

def separable_factors(spec: StencilSpec, tol: float = 1e-12):
    """SVD of the 2-D gather tap matrix: list of (sigma*u, v) band pairs."""
    if spec.ndim != 2:
        raise ValueError("separable factorization implemented for 2-D")
    u, s, vt = np.linalg.svd(spec.gather_coeffs)
    keep = s > tol * s[0] if s[0] > 0 else s > 0
    return [(u[:, p] * s[p], vt[p, :]) for p in np.nonzero(keep)[0]]


def separable_apply(x: jnp.ndarray, spec: StencilSpec,
                    accum_dtype=jnp.float32, tol: float = 1e-12) -> jnp.ndarray:
    """2-D stencil as ``sum_p T_{u_p} @ A @ T_{v_p}^T`` (rank(Cg) slab pairs)."""
    factors = separable_factors(spec, tol)
    r = spec.order
    lead_n = x.ndim - 2
    n_i = x.shape[lead_n] - 2 * r
    n_j = x.shape[lead_n + 1] - 2 * r
    out = None
    prec = contract_precision(x.dtype)
    for ub, vb in factors:
        ti = toeplitz_band(ub, n_i, dtype=accum_dtype)
        tj = toeplitz_band(vb, n_j, dtype=accum_dtype)
        # (..., i+2r, j+2r) -> contract i then j
        tmp = jnp.tensordot(ti, x.astype(accum_dtype), axes=((1,), (lead_n,)),
                            precision=prec)
        tmp = jnp.moveaxis(tmp, 0, lead_n)
        tmp = jnp.tensordot(tmp, tj, axes=((lead_n + 1,), (1,)),
                            precision=prec)
        out = tmp if out is None else out + tmp
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Analysis (§3.4): operator counts and MXU flops
# ---------------------------------------------------------------------------

def matmul_count(cover: LineCover) -> int:
    """Slab matmuls per output block = number of multi-tap lines (single-tap
    lines degrade to scaled shifts — VPU work, no MXU op)."""
    return sum(1 for line in cover.lines if line.nnz > 1)


def mxu_flops(cover: LineCover, block: tuple[int, ...]) -> int:
    """MXU flops to produce one output block via the cover.

    Each multi-tap line contracts an (n, n+2r) Toeplitz against the slab:
    2 * n * (n+2r) * prod(other block dims) flops (mul+add, the paper's
    'full 2n^2 flops per instruction' observation).  Single-tap lines
    contribute VPU flops, counted as 2 * prod(block).
    """
    r = cover.spec.order
    total = 0
    for line in cover.lines:
        if line.is_diagonal or line.nnz <= 1:
            total += 2 * int(np.prod(block)) * max(line.nnz, 1)
            continue
        ax = line.axis
        n = block[ax]
        rest = int(np.prod([b for a, b in enumerate(block) if a != ax]))
        total += 2 * n * (n + 2 * r) * rest
    return total


def separable_mxu_flops(spec: StencilSpec, block: tuple[int, ...]) -> int:
    """MXU flops for the SVD-separable path on one 2-D output block.

    Each rank-1 factor costs two slab matmuls: ``T_u @ A`` over the haloed
    slab and the result against ``T_v^T`` (see :func:`separable_apply`).
    """
    r = spec.order
    n_i, n_j = block[-2], block[-1]
    rank = len(separable_factors(spec))
    per_factor = (2 * n_i * (n_i + 2 * r) * (n_j + 2 * r)
                  + 2 * n_i * (n_j + 2 * r) * n_j)
    return rank * per_factor


def inkernel_mxu_flops(cover: LineCover, block: tuple[int, ...],
                       steps: int) -> int:
    """MXU flops for ``steps`` in-kernel temporally-blocked applications of
    the BASE cover producing one output block (fuse_strategy="inkernel").

    Step ``s`` applies the base operator over the live slab of extent
    ``block + 2*(steps-1-s)*r`` per axis (the halo shrinks by ``r`` per side
    per step), so total work is ``sum_s mxu_flops(cover, live extent)`` —
    linear in T times the base ``(2r+1)``-dense cost plus the shrinking-halo
    overhead, versus the operator-fused ``(2Tr+1)``-dense growth.
    """
    if steps < 1:
        raise ValueError("steps >= 1")
    r = cover.spec.order
    total = 0
    for s in range(steps):
        ext = tuple(b + 2 * (steps - 1 - s) * r for b in block)
        total += mxu_flops(cover, ext)
    return total


def inkernel_hbm_bytes(block: tuple[int, ...], steps: int, order: int,
                       dtype_bytes: int = 4) -> float:
    """HBM bytes for one in-kernel T-step chunk of one block: the
    ``T*r``-haloed read plus one write-back — intermediates stay in VMEM,
    so this equals the operator-fused chunk's traffic exactly."""
    return block_hbm_bytes(block, steps * order, dtype_bytes)


def inkernel_vmem_bytes(block: tuple[int, ...], steps: int, order: int,
                        dtype_bytes: int = 4,
                        cover: LineCover | None = None,
                        batch: int = 1,
                        scratch: str = "pingpong") -> float:
    """VMEM residency of one in-kernel chunk instance: the ``T*r``-deep
    input slab + the output tile (at the problem dtype, per batched
    state), the f32 scratch at the deepest intermediate extent (a
    double-buffered pair for ``scratch="pingpong"``, ONE buffer — half
    the scratch residency — for ``scratch="single"``; batched alongside
    the states), and — when the ``cover`` is known — every step's stacked
    banded Toeplitz operators (broadcast kernel inputs, resident
    simultaneously, SHARED across the batch, and able to dominate at
    large blocks).  The planner's and the temporal chooser's shared
    feasibility bound for fuse_strategy="inkernel"."""
    if steps < 1:
        raise ValueError("steps >= 1")
    n_bufs = 1 if check_scratch(scratch) == "single" else 2
    slab = float(np.prod([b + 2 * steps * order for b in block]))
    buf = float(np.prod([b + 2 * (steps - 1) * order for b in block]))
    out = float(np.prod(block))
    ops = 0.0
    if cover is not None:
        for line in cover.lines:
            if line.is_diagonal or line.nnz <= 1:
                continue
            for s in range(steps):
                n = block[line.axis] + 2 * (steps - 1 - s) * order
                ops += n * (n + 2 * order)
    return (batch * dtype_bytes * (slab + out)
            + 4 * (n_bufs * batch * buf + ops))


def block_hbm_bytes(block: tuple[int, ...], halo_width: int,
                    dtype_bytes: int = 4) -> float:
    """HBM bytes to update one block: haloed read + write-back.

    The shared traffic term of the fuse-depth chooser and the planner's
    roofline model (halo_width = fused order ``T*r``).
    """
    read = float(np.prod([b + 2 * halo_width for b in block]))
    write = float(np.prod(block))
    return dtype_bytes * (read + write)


# ---------------------------------------------------------------------------
# Batched execution (§4.3 input-vector sharing across independent states):
# B states share one kernel instance, one set of Toeplitz band operands and
# ONE dot_general per axis — the states' grid lines stack into the
# contraction's non-contracted matmul dimension.
# ---------------------------------------------------------------------------

#: MXU systolic-array pass granule: each of a matmul's two free
#: dimensions is processed in tiles of this extent, so the slab operand's
#: non-contracted dimension of ``m`` lines occupies ``ceil(m / 128) *
#: 128`` pass slots (the array is symmetric in its free dimensions —
#: "batch-in-M" names the filling of these slots, whichever operand side
#: carries them).
MXU_ROWS = 128


def _mxu_row_pad(rows: int) -> int:
    return int(-(-int(rows) // MXU_ROWS) * MXU_ROWS)


def _batched_line_scale(m_rows: int, batch: int) -> float:
    """Issue-slot ratio of the B-stacked contraction vs B separate ones.

    A single state contributes ``m_rows`` slab lines to the slab-side
    non-contracted dimension of the per-axis ``dot_general``; the MXU
    pads that dimension to the 128-slot pass granule.  Stacking B states
    into the same contraction pads ONCE for ``B * m_rows`` lines instead
    of B times for ``m_rows``, so the modelled flops scale by
    ``pad(B*m) / (B * pad(m)) * B`` — exactly ``B`` when ``m_rows`` is
    granule-aligned, strictly less than ``B`` otherwise (the idle pass
    slots the batch fills).  Reduces to 1.0 at ``batch=1`` so the
    batched model is a strict refinement.
    """
    if batch <= 1:
        return 1.0
    return _mxu_row_pad(batch * m_rows) / float(_mxu_row_pad(m_rows))


def aux_hbm_bytes(block: tuple[int, ...], halo_width: int, n_aux: int,
                  dtype_bytes: int = 4) -> float:
    """Extra HBM bytes per block update for the scenario operands.

    A varying-coefficient field and/or a domain mask is one extra streamed
    read per auxiliary array per chunk: the output-aligned tile for a
    single-step chunk (``halo_width=0``) or the ``T*r``-haloed slab window
    for an in-kernel chunk (the per-step band re-read stays inside VMEM).
    Shared across the batch — states differ, the coefficient field does
    not — so this term does NOT scale with B.
    """
    if n_aux <= 0:
        return 0.0
    return n_aux * dtype_bytes * float(
        np.prod([b + 2 * halo_width for b in block]))


def n_aux_operands(spec: StencilSpec) -> int:
    """How many scenario operands (field, mask) a spec streams per chunk."""
    return int(spec.is_varying) + int(spec.is_masked)


def active_block_fraction(mask: np.ndarray | None,
                          block: tuple[int, ...]) -> float:
    """Fraction of output tiles with at least one active (unmasked) point.

    A fully-masked tile's output is identically zero whatever the operator
    does, so a masked-domain cover may skip it; the planner scales the
    compute and traffic terms by this fraction (pricing-level — runtime
    correctness never depends on the skip, because masked outputs are
    projected to zero anyway).  1.0 for unmasked specs.
    """
    if mask is None:
        return 1.0
    m = np.asarray(mask).astype(bool)
    block = tuple(block[-m.ndim:])
    total = 0
    active = 0
    for idx in np.ndindex(*[-(-s // b) for s, b in zip(m.shape, block)]):
        sl = tuple(slice(i * b, min((i + 1) * b, s))
                   for i, b, s in zip(idx, block, m.shape))
        total += 1
        active += bool(m[sl].any())
    return active / total if total else 1.0


def batched_mxu_flops(cover: LineCover, block: tuple[int, ...],
                      batch: int = 1) -> float:
    """MXU flops for B states sharing one instance's cover application.

    Multi-tap lines scale by :func:`_batched_line_scale` of the per-state
    slab line count (the haloed extents of the non-contracted axes);
    single-tap/diagonal taps are VPU work and scale linearly.  Equals
    :func:`mxu_flops` exactly at ``batch=1``.
    """
    r = cover.spec.order
    total = 0.0
    for line in cover.lines:
        if line.is_diagonal or line.nnz <= 1:
            total += 2 * int(np.prod(block)) * max(line.nnz, 1) * batch
            continue
        ax = line.axis
        n = block[ax]
        rest = int(np.prod([b for a, b in enumerate(block) if a != ax]))
        m_rows = int(np.prod([b + 2 * r for a, b in enumerate(block)
                              if a != ax]))
        total += 2 * n * (n + 2 * r) * rest * _batched_line_scale(m_rows,
                                                                  batch)
    return total


def batched_inkernel_mxu_flops(cover: LineCover, block: tuple[int, ...],
                               steps: int, batch: int = 1) -> float:
    """Batched analogue of :func:`inkernel_mxu_flops`: ``steps`` in-kernel
    applications of the BASE cover over the B-state live slab (per-step
    extents shrink exactly as in the single-state kernel).  Equals
    :func:`inkernel_mxu_flops` at ``batch=1``."""
    if steps < 1:
        raise ValueError("steps >= 1")
    r = cover.spec.order
    total = 0.0
    for s in range(steps):
        ext = tuple(b + 2 * (steps - 1 - s) * r for b in block)
        total += batched_mxu_flops(cover, ext, batch)
    return total


def batched_hbm_bytes(block: tuple[int, ...], halo_width: int,
                      dtype_bytes: int = 4, batch: int = 1) -> float:
    """HBM bytes for one B-state block update: every state carries its own
    haloed read and write-back (states are independent grids), so traffic
    is linear in B — the batch win on the traffic side is the amortized
    per-chunk dispatch overhead, not fewer bytes."""
    return batch * block_hbm_bytes(block, halo_width, dtype_bytes)


def batched_vmem_bytes(block: tuple[int, ...], halo_width: int,
                       dtype_bytes: int = 4, batch: int = 1) -> float:
    """VMEM residency of one B-state instance (haloed slab + output tile
    per state) — the block search's feasibility bound for batched
    problems.  Toeplitz operands are shared across the batch and accounted
    by the inkernel bound where they matter.

    Numerically this equals :func:`batched_hbm_bytes` today — the
    haloed-read + write-back traffic of a chunk IS the slab + tile the
    instance holds resident — so it delegates rather than restating the
    formula: refining either model keeps the other honest.
    """
    return batched_hbm_bytes(block, halo_width, dtype_bytes, batch)
