"""Pallas TPU kernel: stencil matrixization on the MXU (paper §3-§4).

One kernel instance owns one output tile (the SME accumulator-register
analogue, held in VMEM for the whole update — paper observation 1/3).  The
haloed input stays in HBM; each instance DMAs its overlapping ``block+2r``
window into VMEM scratch, rounded up to the (8, 128) tile on the last two
axes so Mosaic accepts the copy (the input is padded so every rounded
window is in bounds).  Shifted sub-slabs replace SME's inter-register
vector assembling (§4.3).
Every multi-tap coefficient line is executed as a banded-Toeplitz
contraction on the MXU (the accumulated sum of the line's ``2r+n`` outer
products, Eq. 12); single-tap lines degrade to VPU scaled-shift adds exactly
as the paper's §3.3 star analysis prescribes.

Line batching (paper §4.3 input-vector sharing): all same-axis Toeplitz
bands are stacked into ONE ``(L*n, n+2r)`` operator and issued as a single
``dot_general`` per axis against the shared haloed slab — the L lines reuse
the same input vectors from one MXU pass, and the per-line results are
peeled off by static row slices afterwards.

Multi-dimensional unrolling (§4.2) = the block shape: a (bi, bj, bk) block
is the paper's ``ui x uk`` unroll with the implicit j-dimension reuse, and
the Python-unrolled line loop below reproduces the §4.3 schedule (one slab
residency, all accumulator updates).

In-kernel temporal blocking (paper §6 x §4.3): ``sweep_pallas_call`` runs T
steps of the BASE operator inside one kernel instance.  The instance owns a
``T*r``-deep haloed slab; each step contracts the per-step Toeplitz set
against the live slab and writes the result to a VMEM scratch buffer
(``scratch="pingpong"`` keeps a double-buffered pair so reads never target
the buffer being written even if Mosaic pipelines the steps;
``scratch="single"`` exploits that each step's input is a fully
materialized value before the write-back and halves the residency),
shrinking the live halo by ``r`` per side per step, and only the final
state is written to HBM.  Intermediates never touch HBM, so MXU work stays
``T x (2r+1)``-dense instead of the operator-fused ``(2Tr+1)``-dense while
the per-chunk traffic is the same single read+write.

Batched execution (§4.3 input-vector sharing across states): both kernels
accept a leading batch axis (``KernelPlan.batch`` / ``SweepKernelPlan
.batch``).  One grid instance then owns the B-state slab for its tile and
the per-axis contraction stays ONE ``dot_general`` — the banded Toeplitz
operand is built once and shared, while the B states' grid lines stack
into the SLAB operand's non-contracted matmul dimension (with the
Toeplitz as LHS that is formally the RHS free dimension; the MXU's
systolic array is symmetric in its two free dimensions and tiles each in
128-wide passes, so "batch-in-M" is used as shorthand for filling those
pass slots).  The per-axis dot count is therefore independent of B,
which is exactly how batching fills the MXU slots that a single small
grid leaves idle.

Each ``pallas_call`` carries a stable ``name`` (``stencil_step``,
``stencil_sweep``) with no shape or depth in it: the compiled custom
call takes that name, so a profiler trace tells the two kernels apart.
A caller may rename a call (``name=``) where the same kernel does a
different job.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import matrixization as mx
from repro.core.coefficient_lines import LineCover
from repro.core.stencil_spec import StencilSpec

__all__ = ["KernelPlan", "build_kernel_plan", "stencil_pallas_call",
           "SweepKernelPlan", "build_sweep_kernel_plan", "sweep_pallas_call",
           "SCRATCH_MODES", "resolve_interpret"]


def resolve_interpret(interpret: bool | None = None) -> bool:
    """The one interpret-or-compile decision for every Pallas kernel.

    ``None`` follows the default backend: the interpreter on the CPU (tests
    and tiny rehearsals), Mosaic on a TPU.  Any other platform has no
    Pallas TPU lowering and is refused.  An explicit ``True`` on a TPU is
    an error, never a slow path; an explicit ``False`` always compiles
    (e.g. lowering for a described TPU topology from a CPU process).
    """
    platform = jax.default_backend()
    if interpret is None:
        if platform == "cpu":
            return True
        if platform == "tpu":
            return False
        raise RuntimeError(f"no Pallas kernel path for platform "
                           f"{platform!r}: compiled on 'tpu', interpreted "
                           f"on 'cpu' only")
    if interpret and platform == "tpu":
        raise ValueError("interpret=True on a TPU would run the Pallas "
                         "interpreter on the host; leave interpret unset")
    return bool(interpret)

# the canonical scratch-mode registry lives with the other temporal-
# blocking policy constants (one definition for engine, planner, kernels)
from repro.core.temporal import SCRATCH_MODES, check_scratch  # noqa: E402


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Host-side compilation of (spec, cover, block) into kernel constants.

    ``batch`` is None for a rank-``ndim`` spatial input; an int B makes the
    kernel expect (and tile over) a leading batch axis of that extent —
    the B states share every Toeplitz operand and each per-axis
    contraction stays one ``dot_general``.
    """

    spec: StencilSpec
    block: tuple[int, ...]
    # multi-tap lines: (axis, toeplitz (block[a], block[a]+2r), fixed gather offsets)
    mat_lines: tuple[tuple[int, np.ndarray, tuple[tuple[int, int], ...]], ...]
    # degenerate taps: (coeff, gather offsets per axis)
    point_taps: tuple[tuple[float, tuple[int, ...]], ...]
    batch: int | None = None
    # scenario operands (coefficient field and/or domain mask): extra
    # OUTPUT-aligned f32 inputs, each multiplied into the accumulator
    # before the write-back (the diag(a) @ T row scale).  Shared across
    # the batch — no leading axis.
    n_aux: int = 0

    @property
    def mxu_dots(self) -> int:
        return len(self.mat_lines)

    @property
    def vpu_taps(self) -> int:
        return len(self.point_taps)

    def axis_groups(self) -> tuple[tuple[int, np.ndarray, tuple[dict, ...]], ...]:
        """Same-axis lines batched: (axis, stacked Toeplitz, per-line fixed).

        The stacked operator is the row-concatenation of the axis's line
        Toeplitzes — one ``(L*n, n+2r)`` matrix contracted ONCE per axis
        (§4.3 input-vector sharing); line ``l``'s rows are the static slice
        ``[l*n, (l+1)*n)`` of the product.
        """
        return _axis_groups(self.mat_lines)


def _axis_groups(mat_lines) -> tuple[tuple[int, np.ndarray, tuple[dict, ...]], ...]:
    groups: dict[int, list] = {}
    for axis, t, fixed in mat_lines:
        groups.setdefault(axis, []).append((t, dict(fixed)))
    out = []
    for axis in sorted(groups):
        ts, fixeds = zip(*groups[axis])
        out.append((axis, np.concatenate(ts, axis=0), tuple(fixeds)))
    return tuple(out)


def _plan_lines(spec: StencilSpec, cover: LineCover):
    """(band_lines, point_taps) kernel constants shared by both kernels.

    ``band_lines`` carry the RAW gather band per multi-tap line —
    ``(axis, (len-2r+1,) band, fixed gather offsets)`` — so callers build
    Toeplitz operators at whatever output extent they need (the
    single-step kernel once at the block, the sweep kernel once per step).
    """
    e = spec.extent
    band_lines = []
    point_taps = []
    for line in cover.lines:
        if line.is_diagonal or line.nnz <= 1:
            # decompose into individual taps (paper §3.3 degenerate case)
            coeffs = np.asarray(line.coeffs)
            for o, c in enumerate(coeffs):
                if c == 0.0:
                    continue
                if line.is_diagonal:
                    offs = {a: (o if d > 0 else e - 1 - o) for a, d in line.axis}
                    for a, v in line.fixed:
                        offs[a] = v
                else:
                    offs = {line.axis: o}
                    for a, v in line.fixed:
                        offs[a] = v
                gather = tuple((e - 1) - offs[a] for a in range(spec.ndim))
                point_taps.append((float(c), gather))
            continue
        band, fixed = mx.line_to_gather_band(line, spec)
        band_lines.append((line.axis, np.asarray(band, np.float64),
                           tuple(sorted(fixed.items()))))
    return tuple(band_lines), tuple(point_taps)


def build_kernel_plan(spec: StencilSpec, cover: LineCover,
                      block: tuple[int, ...],
                      batch: int | None = None) -> KernelPlan:
    if len(block) != spec.ndim:
        raise ValueError(f"block rank {len(block)} != stencil ndim {spec.ndim}")
    if batch is not None and batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    band_lines, point_taps = _plan_lines(spec, cover)
    # numpy path: this runs inside jit traces (plan-per-shape); a
    # jnp intermediate here would be a tracer (see toeplitz_band_np)
    mat_lines = tuple(
        (axis, mx.toeplitz_band_np(band, block[axis]).astype(np.float32),
         fixed)
        for axis, band, fixed in band_lines)
    return KernelPlan(spec=spec, block=tuple(block),
                      mat_lines=mat_lines, point_taps=point_taps,
                      batch=None if batch is None else int(batch),
                      n_aux=mx.n_aux_operands(spec))


#: Scoped-VMEM cap handed to Mosaic.  The compiler's default (16 MiB on a
#: v5e) refuses deep 3-D in-kernel sweeps whose aligned slabs and plane
#: concatenations need more; every chip this repo targets has at least
#: 64 MiB of VMEM (v5e and v6e: 128 MiB).
VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _sublanes(dtype) -> int:
    """Rows of one (sublane x 128) VMEM tile: 8 for 32-bit, 16 for bf16."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _window(ext: Sequence[int], dtype) -> tuple[int, ...]:
    """``ext`` rounded up to the VMEM tile on its last two axes.

    Mosaic refuses a copy or a value whose last two extents are not tile
    multiples (e.g. a ``(130, 130)`` haloed 128-block), so every VMEM
    window and scratch slab is allocated at this aligned extent; only its
    leading ``ext`` part is live.
    """
    ext = list(ext)
    ext[-1] = _round_up(ext[-1], 128)
    if len(ext) >= 2:
        ext[-2] = _round_up(ext[-2], _sublanes(dtype))
    return tuple(ext)


def _contract(x, t, pos: int, k: int, precision=None):
    """``sum_q t[m, q] * x[..., q (at axis pos), ...]`` for ``q < k``.

    The result keeps ``x``'s axes with axis ``pos`` replaced by ``m``.
    ``x``'s last two extents are tile-aligned (:func:`_window`) and every
    contraction is issued as ONE 2-D-shaped ``dot_general`` Mosaic lowers:

    * lane axis: the leading axes fold into the rows (aligned second-minor
      extent) and the band is contracted from the right;
    * any other axis: every plane over the remaining leading axes is
      concatenated along the lanes (each plane a 128-multiple wide), so
      the band multiplies all of them in one pass and the result is split
      back — batch states and the leading spatial planes fill the MXU's
      free dimension (§4.3 input-vector sharing).
    """
    d = x.ndim
    m = t.shape[0]
    f32 = dict(preferred_element_type=jnp.float32, precision=precision)
    if pos == d - 1:
        y = x[..., :k]
        r = jax.lax.dot_general(y.reshape(-1, k), t,
                                (((1,), (1,)), ((), ())), **f32)
        return r.reshape(x.shape[:-1] + (m,))
    if pos < d - 3:
        raise ValueError(f"contraction axis {pos} of a rank-{d} slab: at "
                         f"most one leading axis may precede it")
    major = [a for a in range(d - 2) if a != pos]
    planes = []
    for ix in itertools.product(*(range(x.shape[a]) for a in major)):
        index = [slice(None)] * d
        for a, i in zip(major, ix):
            index[a] = i
        index[pos] = slice(0, k)
        planes.append(x[tuple(index)])
    w = x.shape[-1]
    y = planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=-1)
    r = jax.lax.dot_general(t, y, (((1,), (0,)), ((), ())), **f32)
    if len(planes) == 1:
        return r.reshape(x.shape[:pos] + (m,) + x.shape[pos + 1:])
    parts = jnp.stack([r[..., i * w:(i + 1) * w]
                       for i in range(len(planes))])
    return parts.reshape(tuple(x.shape[a] for a in major) + r.shape[:-1]
                         + (w,))


def _apply_step(slab, *, spec: StencilSpec, out_ext: tuple[int, ...],
                axis_ts: Sequence[jnp.ndarray],
                axis_meta: Sequence[tuple[int, tuple[dict, ...]]],
                point_taps, precision=None) -> jnp.ndarray:
    """One matrixized stencil application of a (VMEM-resident) slab value.

    ``slab`` has extent at least ``out_ext[a] + 2r`` on every spatial axis
    (its last two extents tile-aligned, :func:`_window`; only the leading
    ``out_ext[a] + 2r`` are read), with any leading axes treated as batch;
    the result has extent ``out_ext`` behind the same leading axes.
    ``axis_ts[i]`` is the stacked Toeplitz for ``axis_meta[i] = (axis,
    per-line fixed offsets)`` — ONE ``dot_general`` per axis regardless of
    the batch extent (§4.3 input-vector sharing: the band operand is
    shared and the batch states' lines stack into the contraction's
    non-contracted dimension); per-line terms are separated by static
    slices and trimmed to the output window on the non-contracted axes.
    ``precision`` is the state dtype's :func:`mx.contract_precision`.
    """
    nd, r = spec.ndim, spec.order
    lead = slab.ndim - nd
    out_ext = tuple(out_ext)
    acc = jnp.zeros(slab.shape[:lead] + out_ext, dtype=jnp.float32)
    # leading spatial axes (above the two tiled ones) trim for free
    trim = tuple(slice(0, n + 2 * r) if a < nd - 2 else slice(None)
                 for a, n in enumerate(out_ext))
    slab = slab[(slice(None),) * lead + trim].astype(jnp.float32)
    for t, (axis, fixeds) in zip(axis_ts, axis_meta):
        n_a = out_ext[axis]
        # ONE MXU contraction covers every line on this axis (Eq. 12 sums,
        # batched): the axis's extent becomes the stacked L*n_a rows
        term = _contract(slab, t, lead + axis, n_a + 2 * r, precision)
        for l, fixed_d in enumerate(fixeds):
            index = [slice(None)] * lead
            for a in range(nd):
                if a == axis:
                    index.append(slice(l * n_a, (l + 1) * n_a))
                else:
                    off = fixed_d.get(a, 0)
                    index.append(slice(off, off + out_ext[a]))
            acc = acc + term[tuple(index)]
    for c, gather in point_taps:
        index = (slice(None),) * lead + tuple(
            slice(g, g + n) for g, n in zip(gather, out_ext))
        acc = acc + jnp.float32(c) * slab[index].astype(jnp.float32)
    return acc


def _make_kernel(plan: KernelPlan, out_dtype):
    groups = plan.axis_groups()
    axis_meta = [(axis, fixeds) for axis, _, fixeds in groups]
    n_t = len(groups)
    precision = mx.contract_precision(out_dtype)

    def kernel(x_hbm, *refs):
        t_refs = refs[:n_t]
        aux_refs = refs[n_t:n_t + plan.n_aux]
        o_ref, win, sem = refs[n_t + plan.n_aux:]
        _copy_window(x_hbm, win, sem, plan.block)
        acc = _apply_step(win[...], spec=plan.spec, out_ext=plan.block,
                          axis_ts=[t[...] for t in t_refs],
                          axis_meta=axis_meta, point_taps=plan.point_taps,
                          precision=precision)
        # scenario operands: output-aligned tiles, f32 elementwise scale
        # (diag(a) @ T factored as contract-then-row-scale); aux carries
        # no batch axis, trailing-dim broadcast covers the batched acc
        for a_ref in aux_refs:
            acc = acc * a_ref[...]
        o_ref[...] = acc.astype(out_dtype)

    return kernel


def _broadcast_spec(t: np.ndarray) -> pl.BlockSpec:
    """Whole-array BlockSpec for a kernel constant (same for every grid
    instance).  The zero origin is bound through a default arg — a plain
    ``lambda *ids: (0,) * t.ndim`` would capture the loop variable ``t`` by
    reference and silently use the LAST iteration's rank."""
    return pl.BlockSpec(t.shape, lambda *ids, nd=t.ndim: (0,) * nd)


def _check_batched_input(x, plan, nd, halo_width):
    """Validate the (optionally batched) haloed input; returns (spatial
    out shape, spatial grid)."""
    lead = 0 if plan.batch is None else 1
    if x.ndim != nd + lead:
        kind = f"rank-{nd} spatial" if not lead else \
            f"({plan.batch}, spatial...) batched"
        raise ValueError(f"kernel expects {kind} input, got {x.shape}")
    if lead and x.shape[0] != plan.batch:
        raise ValueError(f"batch extent {x.shape[0]} != planned batch "
                         f"{plan.batch}")
    out_shape = tuple(s - 2 * halo_width for s in x.shape[lead:])
    for s, b in zip(out_shape, plan.block):
        if s % b:
            raise ValueError(f"spatial size {s} not a multiple of block {b}")
    return out_shape, tuple(s // b for s, b in zip(out_shape, plan.block))


def _windowed(x, block, width: int, grid, dtype=None):
    """HBM operand + VMEM window extents for overlapping haloed windows.

    Each grid instance copies the ``block + 2*width`` window at its tile
    origin, rounded up to the VMEM tile (:func:`_window`); the trailing
    edge is zero-padded so the last rounded window stays in bounds (the
    extra rows and lanes are never read into an output).  Leading axes of
    ``x`` beyond ``len(block)`` (the batch) are copied whole.
    """
    nd = len(block)
    lead = x.ndim - nd
    win = _window([b + 2 * width for b in block], dtype or x.dtype)
    need = [(g - 1) * b + n for g, b, n in zip(grid, block, win)]
    pads = [(0, 0)] * lead + [(0, n - s)
                              for n, s in zip(need, x.shape[lead:])]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x, x.shape[:lead] + win


def _copy_window(src_hbm, dst, sem, block) -> None:
    """DMA this grid instance's window of ``src_hbm`` into ``dst``."""
    lead = dst.ndim - len(block)
    index = tuple(pl.ds(0, n) for n in dst.shape[:lead]) + tuple(
        pl.ds(pl.program_id(a) * b, n)
        for a, (b, n) in enumerate(zip(block, dst.shape[lead:])))
    copy = pltpu.make_async_copy(src_hbm.at[index], dst, sem)
    copy.start()
    copy.wait()


def stencil_pallas_call(x: jnp.ndarray, plan: KernelPlan,
                        interpret: bool | None = None,
                        aux: Sequence[jnp.ndarray] = (),
                        name: str = "stencil_step") -> jnp.ndarray:
    """Run the matrixized stencil kernel over a haloed spatial array.

    ``x``: (S_0 + 2r, ..., S_{d-1} + 2r) haloed input; returns (S_0, ...,
    S_{d-1}) valid-mode output.  Spatial sizes must be multiples of the
    block (the ops wrapper pads).  When ``plan.batch`` is set, a leading
    batch axis of that extent precedes the spatial axes on input and
    output: the grid stays spatial (one instance owns every state's tile)
    and the per-axis contraction count does not grow with the batch.

    ``aux``: ``plan.n_aux`` OUTPUT-aligned f32 scenario operands
    (coefficient field, then domain mask), spatial shape == out shape —
    each tiled with the output BlockSpec and multiplied into the
    accumulator (shared across the batch).
    """
    nd, r = plan.spec.ndim, plan.spec.order
    block = plan.block
    out_shape, grid = _check_batched_input(x, plan, nd, r)
    lead = () if plan.batch is None else (plan.batch,)
    if len(aux) != plan.n_aux:
        raise ValueError(f"plan expects {plan.n_aux} aux operand(s), "
                         f"got {len(aux)}")

    x, win = _windowed(x, block, r, grid)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    t_inputs = []
    for _axis, t, _fixeds in plan.axis_groups():
        t_inputs.append(jnp.asarray(t, jnp.float32))
        in_specs.append(_broadcast_spec(t))
    aux_inputs = []
    for a in aux:
        if tuple(a.shape) != out_shape:
            raise ValueError(f"aux operand shape {a.shape} != output "
                             f"spatial shape {out_shape}")
        aux_inputs.append(jnp.asarray(a, jnp.float32))
        in_specs.append(pl.BlockSpec(block, lambda *ids: tuple(ids)))

    out_spec = pl.BlockSpec(lead + block,
                            lambda *ids: (0,) * len(lead) + tuple(ids))
    kernel = _make_kernel(plan, x.dtype)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(lead + out_shape, x.dtype),
        scratch_shapes=[pltpu.VMEM(win, x.dtype),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name=name,
    )(x, *t_inputs, *aux_inputs)


# ---------------------------------------------------------------------------
# In-kernel temporal blocking: T base steps per grid instance, VMEM-resident
# intermediates (the planner's fuse_strategy="inkernel" kernel).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SweepKernelPlan:
    """Host-side compilation of (spec, cover, block, steps).

    ``step_exts[s]`` is the live output extent after step ``s``: the slab
    starts ``steps*r`` deep and every step consumes ``r`` of halo per side,
    so ``step_exts[s][a] = block[a] + 2*(steps-1-s)*r`` and
    ``step_exts[-1] == block``.  ``band_lines``/``point_taps`` describe the
    BASE operator at band level — the same cover applies at every step,
    and each step's Toeplitz set is built from the bands at that step's
    extent (``step_groups``).  ``batch`` follows the :class:`KernelPlan`
    convention (None = no leading axis); ``scratch`` picks the VMEM
    intermediate policy (see :data:`SCRATCH_MODES`).
    """

    spec: StencilSpec
    block: tuple[int, ...]
    steps: int
    # (axis, raw (2r+1,) gather band, fixed gather offsets) per multi-tap line
    band_lines: tuple[tuple[int, np.ndarray, tuple[tuple[int, int], ...]], ...]
    point_taps: tuple[tuple[float, tuple[int, ...]], ...]
    batch: int | None = None
    scratch: str = "pingpong"
    # scenario operands (coefficient field and/or domain mask): extra f32
    # inputs windowed like the x slab (extent block + 2*steps*r, no leading
    # axis — shared across the batch).  Each step multiplies the live
    # accumulator by the static sub-slice at offset (s+1)*r per axis, so
    # every intermediate state is scaled/masked exactly as a sequence of
    # single steps would.
    n_aux: int = 0

    @property
    def step_exts(self) -> tuple[tuple[int, ...], ...]:
        r = self.spec.order
        return tuple(
            tuple(b + 2 * (self.steps - 1 - s) * r for b in self.block)
            for s in range(self.steps))

    def step_groups(self, s: int):
        """Per-axis stacked Toeplitz group at step ``s``'s output extent."""
        ext = self.step_exts[s]
        sized = tuple(
            (axis, mx.toeplitz_band_np(band, ext[axis]).astype(np.float32),
             fixed)
            for axis, band, fixed in self.band_lines)
        return _axis_groups(sized)


def build_sweep_kernel_plan(spec: StencilSpec, cover: LineCover,
                            block: tuple[int, ...],
                            steps: int, batch: int | None = None,
                            scratch: str = "pingpong") -> SweepKernelPlan:
    if len(block) != spec.ndim:
        raise ValueError(f"block rank {len(block)} != stencil ndim {spec.ndim}")
    if steps < 1:
        raise ValueError("steps >= 1")
    if batch is not None and batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    band_lines, point_taps = _plan_lines(spec, cover)
    return SweepKernelPlan(spec=spec, block=tuple(block), steps=int(steps),
                           band_lines=band_lines, point_taps=point_taps,
                           batch=None if batch is None else int(batch),
                           scratch=check_scratch(scratch),
                           n_aux=mx.n_aux_operands(spec))


def _make_sweep_kernel(plan: SweepKernelPlan, out_dtype,
                       step_groups: Sequence[Sequence[tuple]]):
    """``step_groups[s]`` is ``plan.step_groups(s)`` — built ONCE by
    :func:`sweep_pallas_call` (which also feeds the same tensors in as
    kernel inputs, ordered step-major, axis-minor)."""
    spec = plan.spec
    steps = plan.steps
    exts = plan.step_exts
    groups_meta = [[(axis, fixeds) for axis, _t, fixeds in groups]
                   for groups in step_groups]
    n_t = sum(len(g) for g in step_groups)
    n_aux = plan.n_aux
    lead = 0 if plan.batch is None else 1
    r = spec.order
    precision = mx.contract_precision(out_dtype)

    def kernel(x_hbm, *refs):
        t_refs = refs[:n_t]
        aux_hbm = refs[n_t:n_t + n_aux]
        o_ref, win = refs[n_t + n_aux:n_t + n_aux + 2]
        aux_wins = refs[n_t + n_aux + 2:n_t + 2 * n_aux + 2]
        sem = refs[-1]
        bufs = refs[n_t + 2 * n_aux + 2:-1]  # VMEM scratch (pair or single)
        _copy_window(x_hbm, win, sem.at[0], plan.block)
        for i, (a_hbm, a_win) in enumerate(zip(aux_hbm, aux_wins)):
            _copy_window(a_hbm, a_win, sem.at[i + 1], plan.block)
        # the parked slabs are read at their aligned extent: zero them so
        # the never-written margin holds finite values (it only ever
        # reaches discarded output rows and lanes)
        for buf in bufs:
            buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slab = win[...]                # ([batch,] block + 2*steps*r per axis)
        aux_slabs = [a[...] for a in aux_wins]
        pos = 0
        for s in range(steps):
            n_groups = len(step_groups[s])
            acc = _apply_step(
                slab, spec=spec, out_ext=exts[s],
                axis_ts=[t_refs[pos + g][...] for g in range(n_groups)],
                axis_meta=groups_meta[s], point_taps=plan.point_taps,
                precision=precision)
            pos += n_groups
            # scenario scale at EVERY step: step s's live extent sits at
            # offset (s+1)*r per axis inside the aux slab; no leading axis,
            # trailing-dim broadcast covers the batched acc
            for a_slab in aux_slabs:
                index = tuple(slice((s + 1) * r, (s + 1) * r + n)
                              for n in exts[s])
                acc = acc * a_slab[index]
            if s == steps - 1:
                o_ref[...] = acc.astype(out_dtype)
            else:
                # park the shrunk live slab in scratch (never HBM) and read
                # it back as the next step's input; "single" reuses one
                # buffer — acc is a materialized value before the store
                buf = bufs[s % len(bufs)]
                index = (slice(None),) * lead + tuple(
                    slice(0, n) for n in exts[s])
                buf[index] = acc
                slab = buf[...]

    return kernel


def sweep_pallas_call(x: jnp.ndarray, plan: SweepKernelPlan,
                      interpret: bool | None = None,
                      aux: Sequence[jnp.ndarray] = (),
                      name: str = "stencil_sweep") -> jnp.ndarray:
    """Advance a haloed spatial array by ``plan.steps`` base steps in-kernel.

    ``x``: (S_0 + 2*T*r, ..., S_{d-1} + 2*T*r) haloed input; returns
    (S_0, ..., S_{d-1}) — the state after T valid-mode applications.  One
    grid instance owns one output tile plus its ``T*r``-deep slab and runs
    every step in VMEM; only the final state is written back.  With
    ``plan.batch`` set, a leading batch axis precedes the spatial axes
    (the instance owns the B-state slab; scratch buffers batch alongside)
    and the per-step, per-axis contraction count is independent of B.

    ``aux``: ``plan.n_aux`` SLAB-aligned f32 scenario operands (coefficient
    field, then domain mask), each the same spatial shape as ``x`` (no
    leading axis — shared across the batch) and windowed with the same
    overlapping DMA window; the kernel re-reads the right sub-slice at
    every step, so intermediates are scaled/masked per step (the paper's
    banded-operand traffic tax for varying coefficients).
    """
    nd, r = plan.spec.ndim, plan.spec.order
    block, steps = plan.block, plan.steps
    w = steps * r
    out_shape, grid = _check_batched_input(x, plan, nd, w)
    lead = () if plan.batch is None else (plan.batch,)
    if len(aux) != plan.n_aux:
        raise ValueError(f"plan expects {plan.n_aux} aux operand(s), "
                         f"got {len(aux)}")
    slab_shape = tuple(s + 2 * w for s in out_shape)

    x, win = _windowed(x, block, w, grid)
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    t_inputs = []
    step_groups = [plan.step_groups(s) for s in range(steps)]
    for groups in step_groups:
        for _axis, t, _fixeds in groups:
            t_inputs.append(jnp.asarray(t, jnp.float32))
            in_specs.append(_broadcast_spec(t))
    aux_inputs, aux_wins = [], []
    for a in aux:
        if tuple(a.shape) != slab_shape:
            raise ValueError(f"aux operand shape {a.shape} != haloed slab "
                             f"shape {slab_shape}")
        a, a_win = _windowed(jnp.asarray(a, jnp.float32), block, w, grid)
        aux_inputs.append(a)
        aux_wins.append(pltpu.VMEM(a_win, jnp.float32))
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))

    # slab scratch at the deepest intermediate extent: a ping-pong pair by
    # default, one buffer under scratch="single" (half the residency)
    buf_ext = lead + _window([b + 2 * (steps - 1) * r for b in block],
                             jnp.float32)
    n_bufs = 1 if plan.scratch == "single" else 2
    scratch = ([pltpu.VMEM(win, x.dtype)] + aux_wins
               + [pltpu.VMEM(buf_ext, jnp.float32) for _ in range(n_bufs)]
               + [pltpu.SemaphoreType.DMA((1 + len(aux),))])

    out_spec = pl.BlockSpec(lead + block,
                            lambda *ids: (0,) * len(lead) + tuple(ids))
    kernel = _make_sweep_kernel(plan, x.dtype, step_groups)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(lead + out_shape, x.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=resolve_interpret(interpret),
        name=name,
    )(x, *t_inputs, *aux_inputs)
