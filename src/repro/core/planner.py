"""Declarative planning layer: ``StencilProblem -> plan() -> ExecutionPlan
-> compile()``.

The paper's §5.2 leaves "a performance model to determine the optimal
option" as future work.  This module IS that model, made first-class: one
cost function scores every enumerated (cover option x backend x fuse depth)
candidate with roofline terms (MXU compute, HBM traffic, ICI halo traffic),
and the winning decisions are frozen into an :class:`ExecutionPlan` — a
JSON-(de)serializable artifact that records every choice WITH its modelled
cost, renders the full cost table via :meth:`ExecutionPlan.explain`, and
compiles to a jit-ready executable with :func:`compile_plan`.

Decisions recorded per plan:
  * ``option``       — coefficient-line cover of the (fused) operator; for
    ``fuse_strategy="inkernel"`` the cover of the BASE operator, applied at
    every in-kernel step
  * ``base_option``  — cover of the unfused operator (remainder chunks,
    Dirichlet-0 strip fixups)
  * ``backend``      — an entry of the engine's backend registry
  * ``block``        — output tile (the paper's §4.3 in-core block)
  * ``fuse_depth`` / ``fuse_schedule`` — temporal chunking (paper §6)
  * ``fuse_strategy`` — "operator" (compose T steps into one radius-``T*r``
    stencil, flops ``(2Tr+1)``-dense) | "inkernel" (T base-radius steps per
    kernel instance with VMEM-resident intermediates, flops linear in T;
    only for backends registering a ``sweep_builder``).  Both strategies
    carry the same 1-read/1-write-per-chunk HBM traffic
  * ``halo_strategy`` — "none" (valid) | "pad" (single device) |
    "exchange" (mesh: ONE ``T*r``-deep exchange per fused chunk)
  * ``sharding``     — mesh shape/axes + grid axis mapping

Cost model (per fused sweep over the device-local grid, divided by the
chunk depth for a per-original-step figure):
  * t_compute = mxu_flops(fused cover, block) * n_blocks
                / (peak_flops * backend.effective_efficiency(calibration))
                [+ the modelled Dirichlet-0 strip recompute surcharge]
  * t_traffic = block_hbm_bytes(block, T*r) * n_blocks / hbm_bw
                [* the backend's calibrated traffic factor]
  * t_comm    = 2 * T*r * (face area) * dtype_bytes / ici_bw  per sharded
                axis (one deep exchange per chunk)
The chosen candidate minimizes max(t_compute, t_traffic, t_comm) / T; ties
break toward the higher-efficiency backend, then lexicographically, so
plans are deterministic.

Autotuning (DESIGN.md §Autotune) extends the search along two axes:
  * Block search — instead of taking ``default_block``, plan() scores every
    candidate at each MXU-aligned output tile from
    :func:`candidate_blocks`, which enumerates lane/sublane-aligned extents
    clipped to the local grid and prunes them with the same roofline
    helpers (``matrixization.mxu_flops`` / ``separable_mxu_flops`` for the
    optimistic compute term, ``block_hbm_bytes`` for haloed traffic, a VMEM
    residency bound for feasibility).
  * Calibration — ``plan(problem, calibration=record)`` re-ranks the table
    with per-backend factors measured from real compiled executables
    (:mod:`repro.launch.calibrate`): the compute factor scales the
    backend's modelled efficiency, the traffic factor scales t_traffic.
    Every row keeps its uncalibrated score in ``t_model`` so explain()
    renders modelled-vs-calibrated side by side.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
from typing import Any, Callable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import coefficient_lines as cl
from repro.core import halo
from repro.core import matrixization as mx
from repro.core import temporal
from repro.core.engine import (StencilEngine, backend_names, choose_cover,
                               default_block, get_backend, legal_covers,
                               max_fuse_depth_for)
from repro.core.stencil_spec import StencilSpec, from_gather_coeffs

__all__ = ["StencilProblem", "CandidateCost", "ExecutionPlan",
           "CompiledStencil", "plan", "compile_plan", "candidate_cost",
           "candidate_blocks", "best_block", "batch_cost_curve",
           "max_profitable_batch", "serving_buckets", "factor_key",
           "FUSE_STRATEGIES", "PLAN_VERSION", "LAUNCH_OVERHEAD_S"]

PLAN_VERSION = 6

FUSE_STRATEGIES = temporal.FUSE_STRATEGIES

#: Modelled per-fused-chunk dispatch overhead (seconds): kernel launch,
#: grid setup and the band-operand fetch that one chunk pays regardless of
#: how many states it advances.  This is the serving-side term batching
#: amortizes — per STATE it is ``LAUNCH_OVERHEAD_S / (depth * batch)`` —
#: and it is deliberately small against the roofline terms at the report
#: grids so it refines rather than dominates the decision.  Hardware specs
#: may override it via a ``launch_overhead_s`` attribute.
LAUNCH_OVERHEAD_S = 2e-7


# ---------------------------------------------------------------------------
# Problem statement
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class StencilProblem:
    """What to solve, declaratively — the planner decides how.

    Fields:
      spec: the stencil operator (:class:`repro.core.stencil_spec
        .StencilSpec`; build one with ``api.box`` / ``api.star`` /
        ``api.diagonal`` / ``api.from_gather_coeffs``).
      grid: global spatial extents, one per ``spec.ndim`` axis.
      dtype: any numpy/jax dtype name; prices the roofline traffic terms
        and types the compiled executable's expected input.
      boundary: "periodic" | "zero" (Dirichlet-0) | "valid" (shrinking —
        single-step/sweep only, and never distributed).
      steps: how many stencil applications ``compile(plan(...))`` advances
        per call (0 = identity; the fuse schedule covers them exactly).
      batch: how many independent states one compiled call advances
        together (a leading batch axis of the executable's input).  The
        batch is planner-visible — it scales the roofline terms, fills
        the MXU rows a single small grid leaves idle
        (``matrixization.batched_mxu_flops``), amortizes the per-chunk
        dispatch overhead, and tightens the VMEM feasibility bounds — and
        is folded into the kernels' contractions, NOT vmapped (the
        per-axis dot count is independent of it).
      mesh / grid_axes: set together or not at all.  ``mesh`` is a
        ``jax.sharding.Mesh``; ``grid_axes`` names one mesh axis per
        spatial axis ('' for unsharded).  When set, planning is per
        device-local shard and compile() emits the fused distributed
        stepper (one deep halo exchange per fused chunk).

    Example::

        problem = StencilProblem(api.star(2, 2), grid=(256, 256),
                                 boundary="periodic", steps=32)
        run = api.compile(api.plan(problem))
    """

    spec: StencilSpec
    grid: tuple[int, ...]
    dtype: str = "float32"
    boundary: str = "periodic"
    steps: int = 1
    batch: int = 1
    mesh: Any | None = None
    grid_axes: tuple[str, ...] | None = None

    def __post_init__(self):
        halo.check_boundary(self.boundary)
        object.__setattr__(self, "grid", tuple(int(n) for n in self.grid))
        if len(self.grid) != self.spec.ndim:
            raise ValueError(f"grid {self.grid} has {len(self.grid)} axes for "
                             f"a {self.spec.ndim}-D spec")
        if self.steps < 0:
            raise ValueError("steps >= 0")
        object.__setattr__(self, "batch", int(self.batch))
        if self.batch < 1:
            raise ValueError("batch >= 1")
        for name, f in (("coeff_field", self.spec.coeff_field),
                        ("domain_mask", self.spec.domain_mask)):
            if f is not None and tuple(f.shape) != self.grid:
                raise ValueError(f"spec {name} shape {tuple(f.shape)} != "
                                 f"problem grid {self.grid} — scenario "
                                 f"fields live on the problem grid")
        if (self.mesh is None) != (self.grid_axes is None):
            raise ValueError("mesh and grid_axes must be given together")
        if self.mesh is not None and not self.spec.is_constant_dense:
            raise ValueError("distributed planning does not support "
                             "varying-coefficient or masked specs (the deep "
                             "halo exchange does not yet ship the scenario "
                             "fields); plan per device or drop the mesh")
        if self.grid_axes is not None:
            object.__setattr__(self, "grid_axes", tuple(self.grid_axes))
            if len(self.grid_axes) != self.spec.ndim:
                raise ValueError("grid_axes needs one entry per spatial axis")
            if self.boundary == "valid":
                raise ValueError("distributed problems need a "
                                 "shape-preserving boundary")
        jnp.dtype(self.dtype)  # validate

    @property
    def dtype_bytes(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    def mesh_axis_sizes(self) -> dict[str, int]:
        if self.mesh is None:
            return {}
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def local_grid(self) -> tuple[int, ...]:
        """Per-device spatial extents (== grid on a single device)."""
        if self.mesh is None:
            return self.grid
        sizes = self.mesh_axis_sizes()
        out = []
        for n, ax in zip(self.grid, self.grid_axes):
            d = sizes.get(ax, 1) if ax else 1
            if n % d:
                raise ValueError(f"grid extent {n} not divisible by mesh "
                                 f"axis {ax!r} of size {d}")
            out.append(n // d)
        return tuple(out)

    def to_dict(self) -> dict:
        spec_d = {"gather_coeffs": np.asarray(self.spec.gather_coeffs).tolist(),
                  "shape": self.spec.shape,
                  "coefficients": self.spec.coefficients}
        if self.spec.coeff_field is not None:
            spec_d["coeff_field"] = np.asarray(self.spec.coeff_field).tolist()
        if self.spec.domain_mask is not None:
            spec_d["domain_mask"] = np.asarray(self.spec.domain_mask,
                                               np.int8).tolist()
        return {
            "spec": spec_d,
            "grid": list(self.grid),
            "dtype": self.dtype,
            "boundary": self.boundary,
            "steps": int(self.steps),
            "batch": int(self.batch),
        }


# ---------------------------------------------------------------------------
# Cost records
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CandidateCost:
    """Roofline model of one (fuse depth, strategy, cover, backend, block)
    candidate.

    ``t_compute`` / ``t_traffic`` / ``t_comm`` are the CALIBRATED seconds
    per fused sweep of the WHOLE batch (equal to the raw modelled terms
    when the plan carries no calibration); ``t_launch`` is the per-chunk
    dispatch overhead (uncalibrated, additive — serial with the sweep);
    ``t_per_step`` ranks the table and is normalized PER STATE per step:
    ``(max(compute, traffic, comm) + launch) / (depth * batch)`` — the
    quantity the serving loop's throughput inverts.  ``t_model`` always
    holds the uncalibrated per-state-step score, so a calibrated plan
    renders modelled-vs-measured drift per row.  ``strategy`` is the
    temporal execution of the chunk ("operator" fused-operator flops,
    "inkernel" linear-in-T flops; for "inkernel" rows ``option`` names
    the BASE cover applied at every step).
    """
    depth: int
    option: str
    backend: str
    block: tuple[int, ...]  # output tile this row was scored at
    mxu_flops: float        # per fused sweep over the local grid (all states)
    hbm_bytes: float        # per fused sweep over the local grid (all states)
    ici_bytes: float        # per fused chunk (deep halo exchange, all states)
    t_compute: float        # seconds per sweep
    t_traffic: float
    t_comm: float
    t_model: float          # UNcalibrated (max(c, t, m) + launch)/(depth*B)
    t_per_step: float       # calibrated (max(c, t, m) + launch)/(depth*B)
    strategy: str = "operator"
    batch: int = 1          # states advanced together (problem.batch)
    t_launch: float = LAUNCH_OVERHEAD_S   # per-chunk dispatch overhead

    @property
    def key(self) -> tuple:
        """Identity of the decision this row prices (table join key)."""
        return (self.depth, self.option, self.backend, self.block,
                self.strategy)


def _n_blocks(local_grid: Sequence[int], block: Sequence[int]) -> int:
    return int(np.prod([math.ceil(g / b) for g, b in zip(local_grid, block)]))


def _backend_efficiency(name: str) -> float:
    """Modelled efficiency, tolerant of plans shipped from a process that
    had extra backends registered (explain() must not require them)."""
    try:
        return get_backend(name).mxu_efficiency
    except ValueError:
        return 0.0


def _selection_key(c: CandidateCost):
    """Deterministic total order: min bound cost; on a bound tie the
    least total resource use (compute+traffic+comm all still cost energy
    and contend off the critical path), then the higher-efficiency
    backend, then lexicographic."""
    return (c.t_per_step, (c.t_compute + c.t_traffic + c.t_comm) / c.depth,
            -_backend_efficiency(c.backend),
            c.depth, c.strategy, c.option, c.backend, c.block)


def factor_key(backend: str, strategy: str = "operator") -> str:
    """Calibration factor-table key for a (backend, fuse strategy) pair.

    THE single definition of the key format — ``launch.calibrate`` builds
    records with it and :func:`_calib_factor` reads them with it.
    Operator-strategy factors keep the bare backend name (the historical
    per-backend meaning, and the fallback applied when no
    strategy-specific factor was measured); other strategies are keyed
    ``"backend:strategy"`` so the execution paths calibrate independently.
    """
    return backend if strategy == "operator" else f"{backend}:{strategy}"


def _calib_factor(table: Mapping, backend: str, strategy: str):
    """Measured factor for a (backend, strategy), falling back to the
    backend-wide (operator) factor when no strategy-specific one exists."""
    key = factor_key(backend, strategy)
    if key in table:
        return table.get(key)
    return table.get(backend)


def _candidate(spec: StencilSpec, fspec: StencilSpec | None, depth: int,
               option: str, cover: cl.LineCover, backend: str,
               block: tuple[int, ...], local_grid: tuple[int, ...],
               sharded_axes: Sequence[int], boundary: str,
               base_flops: float, dtype_bytes: int, hw,
               calib: Mapping | None = None,
               strategy: str = "operator",
               batch: int = 1) -> CandidateCost:
    be = get_backend(backend)
    if strategy == "inkernel":
        # T base-radius steps in VMEM: flops linear in T (plus the
        # shrinking-halo overhead); ``cover`` is the BASE cover here.
        # Batched: the B states share every per-step contraction.
        flops_block = mx.batched_inkernel_mxu_flops(cover, block, depth,
                                                    batch)
    elif be.flops_model is not None:
        # cover-free backends price per state; no M-fill model for them
        flops_block = be.flops_model(fspec, block) * batch
    else:
        flops_block = mx.batched_mxu_flops(cover, block, batch)
    nb = _n_blocks(local_grid, block)
    flops = float(flops_block) * nb
    if boundary == "zero" and depth > 1:
        # Dirichlet-0 strip fixups: 2 strips per axis, each re-evolved by
        # `depth` unfused steps over a 3*T*r-deep slab (see
        # distributed.distributed_fused_chunk) — modelled as that fraction
        # of `depth` full unfused sweeps.  Both strategies share the fixup;
        # every batched state pays it.
        frac = min(1.0, 3 * depth * spec.order / min(local_grid))
        flops += 2 * spec.ndim * depth * frac * base_flops * batch
    # one T*r-deep haloed read + one write per chunk PER STATE — identical
    # traffic for both strategies (in-kernel intermediates never touch HBM)
    bytes_hbm = mx.batched_hbm_bytes(block, depth * spec.order,
                                     dtype_bytes, batch) * nb
    # varying/masked band traffic: the per-point field (and mask) is read
    # once per chunk alongside the state — f32, haloed to the chunk depth,
    # NOT batch-scaled (the fields are shared across all states)
    n_aux = mx.n_aux_operands(spec)
    if n_aux:
        bytes_hbm += mx.aux_hbm_bytes(block, depth * spec.order, n_aux) * nb
    # masked-domain cover: tiles with no active point skip both the
    # contraction and the write-back — modelled as the active-tile fraction
    # scaling compute and traffic (pricing only; execution is exact either
    # way since the mask zeroes the skipped outputs)
    active = mx.active_block_fraction(spec.domain_mask, block)
    if active < 1.0:
        flops *= active
        bytes_hbm *= active
    ici = 0.0
    for a in sharded_axes:
        face = float(np.prod([g for i, g in enumerate(local_grid) if i != a]))
        ici += 2 * depth * spec.order * face * dtype_bytes * batch
    t_launch = float(getattr(hw, "launch_overhead_s", LAUNCH_OVERHEAD_S))
    per = depth * batch
    t_compute_raw = flops / (hw.peak_flops_bf16 * be.mxu_efficiency)
    t_traffic_raw = bytes_hbm / hw.hbm_bw
    t_comm = ici / hw.ici_bw if ici else 0.0
    if calib is not None:
        cfac = _calib_factor(calib.get("compute", {}), backend, strategy)
        eff = be.effective_efficiency(
            {backend: cfac} if cfac is not None else None)
        t_compute = flops / (hw.peak_flops_bf16 * eff)
        tfac = _calib_factor(calib.get("traffic", {}), backend, strategy)
        t_traffic = t_traffic_raw * float(1.0 if tfac is None else tfac)
    else:
        t_compute, t_traffic = t_compute_raw, t_traffic_raw
    return CandidateCost(depth=depth, option=option, backend=backend,
                         block=tuple(block), strategy=strategy, batch=batch,
                         mxu_flops=flops, hbm_bytes=bytes_hbm, ici_bytes=ici,
                         t_compute=t_compute, t_traffic=t_traffic,
                         t_comm=t_comm, t_launch=t_launch,
                         t_model=(max(t_compute_raw, t_traffic_raw, t_comm)
                                  + t_launch) / per,
                         t_per_step=(max(t_compute, t_traffic, t_comm)
                                     + t_launch) / per)


# ---------------------------------------------------------------------------
# Block search (DESIGN.md §Autotune)
# ---------------------------------------------------------------------------

# haloed read + output tile resident; shared with the temporal chooser
# (see matrixization.VMEM_BUDGET)
_VMEM_BYTES = mx.VMEM_BYTES
_VMEM_BUDGET = mx.VMEM_BUDGET

# Per-axis aligned extents: the minormost axis stays a multiple of the
# 128-wide lane dimension, the second-to-minor of the 8-deep sublane; the
# leading 3-D axis is the sequential-grid axis, where small tiles amortize
# nothing and large ones only cut halo re-reads.
_ALIGNED_EXTENTS = {
    1: ((128, 256, 512),),
    2: ((32, 64, 128, 256, 512), (128, 256)),
    3: ((4, 8, 16, 32, 64), (32, 64, 128), (128, 256)),
}


def _ranked_blocks(spec: StencilSpec, local_grid: Sequence[int],
                   hw, dtype_bytes: int, halo_width: int | None,
                   batch: int = 1
                   ) -> tuple[list[tuple[int, ...]], tuple[int, ...]]:
    """Shared enumeration for :func:`candidate_blocks` / :func:`best_block`:
    (every feasible aligned tile in roofline-score order — best first,
    the clipped default block).  ``batch`` scales the VMEM feasibility
    bound: a batched instance holds every state's haloed tile."""
    nd = spec.ndim
    r = spec.order
    if halo_width is None:
        halo_width = r
    default = tuple(min(b, int(g)) for b, g in
                    zip(default_block(spec), local_grid))
    extents = _ALIGNED_EXTENTS.get(nd)
    if extents is None:               # ndim > 3: no aligned table, no search
        return [default], default
    sizes = [sorted({min(int(s), int(g)) for s in ext} | {d})
             for ext, g, d in zip(extents, local_grid, default)]
    blocks = {tuple(b) for b in itertools.product(*sizes)}
    blocks.add(default)

    bytes_of = {blk: mx.block_hbm_bytes(blk, halo_width, dtype_bytes)
                for blk in blocks}
    feasible = sorted(
        b for b in blocks
        if mx.batched_vmem_bytes(b, halo_width, dtype_bytes,
                                 batch) <= _VMEM_BUDGET) or [default]
    covers = [cl.make_cover(spec, o) for o in legal_covers(spec)]

    def score(blk):
        # batch-aware: the M-fill term can shift the compute/traffic
        # balance per tile, and the shortlist cut must see the same
        # model the candidate loop scores with (per state, per element)
        flops = min(mx.batched_mxu_flops(cover, blk, batch)
                    for cover in covers)
        if nd == 2 and spec.is_constant_dense:
            flops = min(flops, mx.separable_mxu_flops(spec, blk) * batch)
        t_c = flops / hw.peak_flops_bf16
        t_t = batch * bytes_of[blk] / hw.hbm_bw
        return max(t_c, t_t) / float(batch * np.prod(blk))

    return sorted(feasible, key=lambda b: (score(b), b)), default


def candidate_blocks(spec: StencilSpec, local_grid: Sequence[int],
                     hw=None, dtype_bytes: int = 4, *,
                     halo_width: int | None = None,
                     max_blocks: int = 4,
                     batch: int = 1) -> list[tuple[int, ...]]:
    """MXU-aligned candidate output tiles for the planner's block search.

    Enumerates the cartesian product of lane/sublane-aligned per-axis
    extents (clipped to the device-local grid), then prunes:

      1. *feasibility* — the haloed input tile plus the output tile must
         fit the VMEM residency budget (``block_hbm_bytes`` at
         ``halo_width``, default the unfused ``spec.order``);
      2. *roofline score* — per output element, the max of the optimistic
         compute term (cheapest legal cover via ``matrixization.mxu_flops``,
         and for 2-D also ``separable_mxu_flops``) and the haloed HBM
         traffic term; only the best ``max_blocks`` tiles survive.

    The clipped ``default_block`` is always in the result, so the search
    can never do worse than the pre-autotune planner.  Deterministic: the
    result is sorted and depends only on the arguments.
    """
    if hw is None:
        hw = _default_hw()
    ranked, default = _ranked_blocks(spec, local_grid, hw, dtype_bytes,
                                     halo_width, batch)
    keep = ranked[:max(1, int(max_blocks))]
    if default not in keep:
        keep[-1] = default
    return sorted(keep)


def best_block(spec: StencilSpec, local_grid: Sequence[int],
               hw=None, dtype_bytes: int = 4, *,
               halo_width: int | None = None,
               batch: int = 1) -> tuple[int, ...]:
    """The top-ranked tile of the block search (the kernel wrappers'
    default when no block is pinned — see ``kernels.ops``): the same
    enumeration and roofline pruning as :func:`candidate_blocks`, returning
    the best-scoring tile instead of the sorted shortlist."""
    if hw is None:
        hw = _default_hw()
    ranked, _ = _ranked_blocks(spec, local_grid, hw, dtype_bytes, halo_width,
                               batch)
    return ranked[0]


# ---------------------------------------------------------------------------
# ExecutionPlan — the frozen decision record
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Every decision the planner made, with its modelled cost.

    Frozen and JSON-round-trippable by construction: all fields are
    JSON-native containers (the spec lives inside ``problem`` as a nested
    coefficient list), so ``from_json(to_json(p)) == p`` under dataclass
    equality.  The plan is the unit of reproducibility — ship it, diff it,
    golden-test it (``make plan-report``).
    """

    version: int
    problem: dict
    hw: dict
    option: str            # cover of the fused operator at fuse_depth
    #                        (BASE cover when fuse_strategy="inkernel")
    base_option: str       # cover of the unfused operator
    backend: str
    block: tuple[int, ...]
    unroll: tuple[int, ...]
    fuse_depth: int
    fuse_schedule: tuple[int, ...]
    fuse_strategy: str     # "operator" | "inkernel"
    halo_strategy: str     # "none" | "pad" | "exchange"
    halo_width: int
    sharding: dict | None
    candidates: tuple[CandidateCost, ...]
    calibration: dict | None = None   # measured per-backend factor summary
    #   {"hw": str, "compute": {backend: measured/modelled flops},
    #    "traffic": {backend: measured/modelled bytes}} — see
    #   repro.launch.calibrate.CalibrationRecord

    # -- reconstruction ----------------------------------------------------
    @property
    def spec(self) -> StencilSpec:
        s = self.problem["spec"]
        field = s.get("coeff_field")
        mask = s.get("domain_mask")
        return from_gather_coeffs(
            np.asarray(s["gather_coeffs"]), s["shape"],
            coefficients=s.get("coefficients", "constant"),
            coeff_field=None if field is None else np.asarray(field),
            domain_mask=None if mask is None else np.asarray(mask, bool))

    @property
    def steps(self) -> int:
        return int(self.problem["steps"])

    @property
    def batch(self) -> int:
        # plans from PLAN_VERSION < 4 never serialized a batch; those
        # cannot be deserialized here (version guard), so the key is
        # always present — .get keeps hand-built problem dicts working
        return int(self.problem.get("batch", 1))

    @property
    def boundary(self) -> str:
        return self.problem["boundary"]

    @property
    def grid(self) -> tuple[int, ...]:
        return tuple(self.problem["grid"])

    def chosen(self) -> CandidateCost:
        for c in self.candidates:
            if c.key == (self.fuse_depth, self.option, self.backend,
                         self.block, self.fuse_strategy):
                return c
        raise KeyError("chosen candidate missing from the cost table")

    def ranked(self) -> tuple[CandidateCost, ...]:
        """The cost table in selection order (best candidate first)."""
        return tuple(sorted(self.candidates, key=_selection_key))

    # -- serialization -----------------------------------------------------
    def to_json(self, indent: int | None = None) -> str:
        d = dataclasses.asdict(self)
        d["block"] = list(self.block)
        d["unroll"] = list(self.unroll)
        d["fuse_schedule"] = list(self.fuse_schedule)
        d["candidates"] = [dict(dataclasses.asdict(c), block=list(c.block))
                           for c in self.candidates]
        return json.dumps(d, indent=indent)

    @classmethod
    def from_json(cls, text: str) -> "ExecutionPlan":
        d = json.loads(text)
        if d.get("version") != PLAN_VERSION:
            raise ValueError(f"plan version {d.get('version')!r} does not "
                             f"match this code's PLAN_VERSION={PLAN_VERSION};"
                             f" re-plan the problem")
        d["block"] = tuple(d["block"])
        d["unroll"] = tuple(d["unroll"])
        d["fuse_schedule"] = tuple(d["fuse_schedule"])
        d["candidates"] = tuple(
            CandidateCost(**dict(c, block=tuple(c["block"])))
            for c in d["candidates"])
        return cls(**d)

    # -- reporting ---------------------------------------------------------
    def schedule_str(self) -> str:
        if not self.fuse_schedule:
            return "[]"
        full = sum(1 for t in self.fuse_schedule if t == self.fuse_depth)
        rem = [t for t in self.fuse_schedule if t != self.fuse_depth]
        s = f"{self.fuse_depth}x{full}"
        if rem:
            s += "+" + "+".join(str(t) for t in rem)
        return s

    def explain(self, top: int = 8) -> str:
        """Human-readable decision record with the modelled cost table.

        Column meanings (one row per enumerated candidate, best first):
        ``depth`` fused-chunk length T, ``batch`` states advanced together
        (the problem's batch — every row of one plan shares it), ``strat``
        temporal strategy of the chunk ("operator" fused-operator |
        "inkernel" T VMEM-resident base steps), ``coeff`` coefficient kind
        of the spec ("const" | "vary" | "mask" | "vary+mask" — shared by
        every row; varying/masked rows already carry the band-traffic tax
        and the masked active-tile fraction in their scores), ``cover``
        coefficient-line cover of the T-fused operator (of the BASE
        operator for inkernel rows), ``backend`` registry entry, ``block``
        output tile the row was scored at,
        ``t_compute``/``t_traffic``/``t_comm`` calibrated roofline seconds
        per fused sweep of the whole batch, ``t/model`` the UNcalibrated
        per-state-step score, ``t/step`` the calibrated per-STATE-per-step
        score the ranking minimizes (the two columns coincide when the
        plan carries no calibration).

        For varying/masked specs a ``fusion legality`` line states the
        fallback decision explicitly: which (strategy, depth) pairs were
        excluded and why, so a depth-1 plan is visibly a LEGAL fallback
        rather than a cost-model preference.
        """
        p = self.problem
        spec = self.spec
        sh = self.sharding
        mesh_s = ("-" if sh is None else
                  "x".join(str(n) for n in sh["mesh_shape"]) + "("
                  + ",".join(a if a else "." for a in sh["grid_axes"]) + ")")
        ch = self.chosen()
        lines = [
            f"ExecutionPlan v{self.version}: {spec.describe()} | "
            f"grid={tuple(p['grid'])} {p['dtype']} | boundary={p['boundary']} "
            f"| steps={p['steps']} | batch={self.batch} | mesh={mesh_s}",
            f"hw {self.hw['name']}: {self.hw['peak_flops_bf16'] / 1e12:.0f} "
            f"TFLOP/s peak, {self.hw['hbm_bw'] / 1e9:.0f} GB/s HBM, "
            f"{self.hw['ici_bw'] / 1e9:.0f} GB/s ICI",
            f"chosen: backend={self.backend} cover={self.option} "
            f"(base {self.base_option}) block={self.block} "
            f"fuse={self.fuse_depth} strategy={self.fuse_strategy} "
            f"schedule={self.schedule_str()} "
            f"halo={self.halo_strategy} width={self.halo_width}",
            f"{'modelled' if self.calibration is None else 'calibrated'}"
            f"/state-step: "
            f"compute {ch.t_compute / (ch.depth * ch.batch):.3e}s, "
            f"traffic {ch.t_traffic / (ch.depth * ch.batch):.3e}s, "
            f"comm {ch.t_comm / (ch.depth * ch.batch):.3e}s, "
            f"launch {ch.t_launch / (ch.depth * ch.batch):.3e}s "
            f"-> {ch.t_per_step:.3e}s",
        ]
        if self.calibration is not None:
            cal = self.calibration
            facts = " ".join(
                f"{be}:x{cal['compute'].get(be, 1.0):.2f}/"
                f"x{cal['traffic'].get(be, 1.0):.2f}"
                for be in sorted(set(cal["compute"]) | set(cal["traffic"])))
            lines.append(f"calibrated ({cal.get('hw', '?')} measured, "
                         f"compute/traffic factors): {facts}")
        coeff_kind = ("const" if spec.is_constant_dense else "+".join(
            (["vary"] if spec.is_varying else [])
            + (["mask"] if spec.is_masked else [])))
        if not spec.is_constant_dense:
            from repro.core.temporal import fusion_legal
            ink = fusion_legal(spec, self.boundary, "inkernel", 2)
            lines.append(
                f"fusion legality ({coeff_kind}): operator depth>1 excluded "
                f"(per-step scale does not compose); inkernel depth>1 "
                + (f"legal at boundary={self.boundary!r}" if ink else
                   f"excluded at boundary={self.boundary!r} -> depth-1 "
                   f"fallback"))
        lines.append(
            "  rank depth batch strat    coeff     cover       backend     "
            "block        t_compute   t_traffic   t_comm      t/model     "
            "t/step")
        ranked = self.ranked()
        for i, c in enumerate(ranked[:top]):
            mark = "  <- chosen" if c.key == (
                self.fuse_depth, self.option, self.backend, self.block,
                self.fuse_strategy) else ""
            blk = "x".join(str(b) for b in c.block)
            lines.append(
                f"  {i + 1:4d} {c.depth:5d} {c.batch:5d} {c.strategy:<8s} "
                f"{coeff_kind:<9s} "
                f"{c.option:<11s} {c.backend:<11s} "
                f"{blk:<12s} "
                f"{c.t_compute:.3e}   {c.t_traffic:.3e}   {c.t_comm:.3e}   "
                f"{c.t_model:.3e}   {c.t_per_step:.3e}{mark}")
        if len(ranked) > top:
            lines.append(f"  ... {len(ranked) - top} more candidates")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# plan()
# ---------------------------------------------------------------------------

def _hw_dict(hw) -> dict:
    # launch_overhead_s is recorded even at its default: every term that
    # shaped the scores must be reconstructible from the plan JSON alone
    return {"name": hw.name, "peak_flops_bf16": float(hw.peak_flops_bf16),
            "hbm_bw": float(hw.hbm_bw), "ici_bw": float(hw.ici_bw),
            "hbm_bytes": float(hw.hbm_bytes),
            "launch_overhead_s": float(getattr(hw, "launch_overhead_s",
                                               LAUNCH_OVERHEAD_S))}


def _default_hw():
    """The attached chip's roofline constants on a TPU (an unknown
    ``device_kind`` raises); the TPU v5e everywhere else, so CPU
    rehearsals and the golden report plan for the chip this repo targets."""
    from repro.launch.mesh import TPU_V5E, hardware_for_device
    if jax.default_backend() == "tpu":
        return hardware_for_device()
    return TPU_V5E


def _sharded_axes(problem: StencilProblem) -> list[int]:
    if problem.grid_axes is None:
        return []
    sizes = problem.mesh_axis_sizes()
    return [i for i, ax in enumerate(problem.grid_axes)
            if ax and sizes.get(ax, 1) > 1]


def _base_stats(spec: StencilSpec, block: tuple[int, ...],
                local_grid: tuple[int, ...],
                option: str | None) -> tuple[str, float]:
    """(base cover, unfused-sweep flops) at one block — the shared
    plan()/candidate_cost() path, so the Dirichlet-0 strip surcharge (which
    is priced in unfused sweeps) cannot drift between the two."""
    base_option, base_cover = ((option, cl.make_cover(spec, option))
                               if option else choose_cover(spec, block[0]))
    base_flops = float(mx.mxu_flops(base_cover, block)) * _n_blocks(local_grid,
                                                                    block)
    return base_option, base_flops


def _calibration_dict(calibration) -> dict | None:
    """Normalize plan()'s ``calibration`` input to the JSON-native summary
    stored on the plan: a ``CalibrationRecord``, an equivalent mapping, or
    None.  Duck-typed so ``core`` never imports ``launch``."""
    if calibration is None:
        return None
    if isinstance(calibration, Mapping):
        hw = calibration.get("hw", "")
        compute = calibration.get("compute", {})
        traffic = calibration.get("traffic", {})
    else:
        hw = getattr(calibration, "hw", "")
        compute = calibration.compute
        traffic = calibration.traffic
    return {"hw": str(hw),
            "compute": {k: float(v) for k, v in sorted(compute.items())},
            "traffic": {k: float(v) for k, v in sorted(traffic.items())}}


def _feasible_depth(boundary: str, r: int, n_min: int, steps: int) -> int:
    """Hard feasibility cap (shape + boundary + step count) — shared with
    the engine via :func:`repro.core.engine.max_fuse_depth_for` so a
    planned depth is never one the execution layer rejects."""
    if steps <= 1:
        return 1
    return max(1, min(steps, max_fuse_depth_for(boundary, max(r, 1), n_min)))


def plan(problem: StencilProblem, hw=None, *,
         backends: Sequence[str] | None = None,
         option: str | None = None,
         fuse: int | None = None,
         fuse_strategy: str | None = None,
         block: tuple[int, ...] | None = None,
         max_depth: int = 4,
         max_blocks: int = 4,
         calibration=None) -> ExecutionPlan:
    """Enumerate (cover x backend x fuse x block x strategy) candidates,
    pick the min-cost one.

    ``option`` / ``backends`` / ``fuse`` / ``fuse_strategy`` / ``block``
    pin a decision instead of searching it (the pinned value still gets its
    cost modelled and recorded).  A pinned ``option`` constrains the
    UNFUSED operator; fused operators are re-covered per depth, exactly as
    the engine's sweep does (inkernel candidates keep the base cover — it
    is applied at every in-kernel step).  Without a ``block`` pin the
    search scores every tile from :func:`candidate_blocks` (at most
    ``max_blocks`` of them); inkernel candidates are additionally pruned by
    the deep-slab VMEM residency (``matrixization.inkernel_vmem_bytes``).

    ``calibration`` re-ranks the table with measured per-backend factors
    (a :class:`repro.launch.calibrate.CalibrationRecord` or an equivalent
    mapping); the uncalibrated score is kept per row in
    ``CandidateCost.t_model`` and the factor summary is frozen into the
    plan's ``calibration`` field.
    """
    if hw is None:
        hw = _default_hw()
    spec = problem.spec
    r = spec.order

    names = list(backends) if backends is not None else backend_names()
    for nm in names:
        get_backend(nm)  # fail fast on unknown names
    if option is not None and option not in cl.COVER_OPTIONS:
        raise ValueError(f"unknown cover option {option!r}; choose from "
                         f"{list(cl.COVER_OPTIONS)}")
    if fuse_strategy is not None and fuse_strategy not in FUSE_STRATEGIES:
        raise ValueError(f"unknown fuse strategy {fuse_strategy!r}; choose "
                         f"from {FUSE_STRATEGIES}")
    strategies = (FUSE_STRATEGIES if fuse_strategy is None
                  else (fuse_strategy,))
    if fuse_strategy == "inkernel" and not any(
            get_backend(nm).sweep_builder is not None
            and get_backend(nm).supports(problem.spec) for nm in names):
        raise ValueError(
            f"fuse_strategy='inkernel' pinned but no backend in {names} "
            f"registers a sweep_builder supporting this spec "
            f"(see register_backend)")

    local_grid = problem.local_grid()
    sharded_axes = _sharded_axes(problem)
    calib = _calibration_dict(calibration)
    if block is not None:
        blocks = [tuple(int(b) for b in block)]
    else:
        blocks = candidate_blocks(spec, local_grid, hw, problem.dtype_bytes,
                                  max_blocks=max_blocks,
                                  batch=problem.batch)
    base_stats = {blk: _base_stats(spec, blk, local_grid, option)
                  for blk in blocks}

    feasible = _feasible_depth(problem.boundary, r, min(local_grid),
                               problem.steps)
    if fuse is not None:
        # a pin is checked against FEASIBILITY only — max_depth is a
        # search-enumeration width, not a legality bound
        if fuse < 1:
            raise ValueError(f"fuse depth must be >= 1, got {fuse}")
        if fuse > max(feasible, 1):
            raise ValueError(f"fuse depth {fuse} exceeds the shape/boundary "
                             f"cap {feasible} for grid {local_grid}")
        depths = [int(fuse)]
    else:
        depths = list(range(1, min(feasible, max_depth) + 1))

    fused_specs: dict[int, StencilSpec] = {1: spec}
    base_opts = [option] if option else legal_covers(spec)
    base_covers = {opt: cl.make_cover(spec, opt) for opt in base_opts}
    cands: list[CandidateCost] = []
    for t in depths:
        # depth 1 has no strategy (a chunk of one step IS the base
        # operator), so the baseline row is enumerated even under a
        # pinned-inkernel search — mirroring temporal.choose_fuse_depth.
        # fusion_legal gates BOTH branches: a varying/masked spec never
        # gets an operator row at t > 1 (the fused correlation cannot
        # express the per-step scale) nor an inkernel row the boundary
        # makes inexact — the planner cannot emit an illegal pair.
        if ("operator" in strategies or t == 1) and \
                temporal.fusion_legal(spec, problem.boundary, "operator", t):
            fspec = fused_specs.get(t)
            if fspec is None:
                fspec = temporal.fuse_steps(spec, t)
                fused_specs[t] = fspec
            if t == 1 and option:
                opts = [option]
            else:
                opts = legal_covers(fspec)
            for oi, opt in enumerate(opts):
                cover = cl.make_cover(fspec, opt)
                for nm in names:
                    be = get_backend(nm)
                    if not be.supports(fspec):
                        continue
                    if not be.uses_cover and oi > 0:
                        continue  # cover-free execution: one row per depth
                    for blk in blocks:
                        cands.append(_candidate(
                            spec, fspec, t, opt, cover, nm, blk, local_grid,
                            sharded_axes, problem.boundary,
                            base_stats[blk][1], problem.dtype_bytes, hw,
                            calib, batch=problem.batch))
        if "inkernel" in strategies and t > 1 and \
                temporal.fusion_legal(spec, problem.boundary, "inkernel", t):
            # T base-radius steps per kernel instance: the cover is the
            # BASE spec's (re-applied every step), only backends with a
            # registered sweep_builder can execute it, and the deep slab
            # plus the double-buffered intermediates must stay VMEM-resident
            for oi, opt in enumerate(base_opts):
                cover = base_covers[opt]
                for nm in names:
                    be = get_backend(nm)
                    if be.sweep_builder is None or not be.supports(spec):
                        continue
                    if not be.uses_cover and oi > 0:
                        continue
                    for blk in blocks:
                        if mx.inkernel_vmem_bytes(
                                blk, t, r, problem.dtype_bytes,
                                cover=cover,
                                batch=problem.batch) > _VMEM_BUDGET:
                            continue
                        cands.append(_candidate(
                            spec, None, t, opt, cover, nm, blk, local_grid,
                            sharded_axes, problem.boundary,
                            base_stats[blk][1], problem.dtype_bytes, hw,
                            calib, strategy="inkernel",
                            batch=problem.batch))
    if not cands:
        raise ValueError("no feasible (cover x backend x fuse x strategy) "
                         "candidate — check the backend/strategy pins "
                         "against the spec")

    best = min(cands, key=_selection_key)
    depth = best.depth if problem.steps else 1
    block = best.block
    base_option = base_stats[block][0]
    if depth == 1 or best.strategy == "inkernel":
        # depth 1: fused and unfused operator coincide; inkernel: the
        # chunk re-applies the base cover per step — either way the record
        # must match what compile() executes
        base_option = best.option
    schedule = tuple(temporal.fuse_schedule(problem.steps, depth))

    if problem.boundary == "valid":
        halo_strategy = "none"
    elif problem.mesh is not None:
        # the compiled stepper exchanges on EVERY named mesh axis (size-1
        # axes permute to themselves, carrying no wire traffic — t_comm
        # already reflects that), so the record matches the executable
        halo_strategy = "exchange"
    else:
        halo_strategy = "pad"
    sharding = None
    if problem.mesh is not None:
        sharding = {"mesh_shape": [int(n) for n in problem.mesh.devices.shape],
                    "mesh_axes": list(problem.mesh.axis_names),
                    "grid_axes": list(problem.grid_axes)}

    return ExecutionPlan(
        version=PLAN_VERSION,
        problem=problem.to_dict(),
        hw=_hw_dict(hw),
        option=best.option,
        base_option=base_option,
        backend=best.backend,
        block=block,
        unroll=(1,) * spec.ndim,
        fuse_depth=depth,
        fuse_schedule=schedule,
        fuse_strategy=best.strategy if depth > 1 else "operator",
        halo_strategy=halo_strategy,
        halo_width=depth * r,
        sharding=sharding,
        candidates=tuple(cands),
        calibration=calib,
    )


def candidate_cost(problem: StencilProblem, depth: int, option: str,
                   backend: str, hw=None,
                   block: tuple[int, ...] | None = None,
                   base_option: str | None = None,
                   strategy: str = "operator",
                   calibration=None) -> CandidateCost:
    """Model one candidate independently (the property-test entry point).

    ``base_option`` and ``calibration`` must match what was given to
    ``plan()`` (if anything) for the Dirichlet-0 strip surcharge and the
    calibrated terms to agree with the plan's own table — both paths share
    :func:`_base_stats` and :func:`_candidate`.  For
    ``strategy="inkernel"``, ``option`` names the BASE cover (applied at
    every in-kernel step).
    """
    if hw is None:
        hw = _default_hw()
    spec = problem.spec
    local_grid = problem.local_grid()
    if block is None:
        block = tuple(min(b, g) for b, g in
                      zip(default_block(spec), local_grid))
    block = tuple(int(b) for b in block)
    _, base_flops = _base_stats(spec, block, local_grid, base_option)
    if strategy == "inkernel":
        fspec, cover = None, cl.make_cover(spec, option)
    else:
        fspec = spec if depth == 1 else temporal.fuse_steps(spec, depth)
        cover = cl.make_cover(fspec, option)
    return _candidate(spec, fspec, depth, option, cover, backend, block,
                      local_grid, _sharded_axes(problem), problem.boundary,
                      base_flops, problem.dtype_bytes, hw,
                      _calibration_dict(calibration), strategy=strategy,
                      batch=problem.batch)


# ---------------------------------------------------------------------------
# Serving admission: the batch bucket-cliff query
# ---------------------------------------------------------------------------

def serving_buckets(max_batch: int) -> list[int]:
    """The batch bucket sizes a serving loop compiles for a ``max_batch``
    cap: powers of two plus the cap itself (matching the bucket round-up
    in ``launch.serve_stencil``), ascending."""
    if max_batch < 1:
        raise ValueError("max_batch >= 1")
    bs = [1]
    while bs[-1] * 2 < max_batch:
        bs.append(bs[-1] * 2)
    if max_batch > 1:
        bs.append(int(max_batch))
    return bs


def batch_cost_curve(problem: StencilProblem, max_batch: int, hw=None, *,
                     plan_fn: Callable | None = None,
                     **plan_kwargs) -> dict[int, float]:
    """Modelled per-STATE cost of ``problem`` at every serving bucket.

    Plans ``problem`` at each bucket of :func:`serving_buckets` (the
    problem's own ``batch`` is ignored) and returns ``{bucket:
    chosen t_per_step}`` — the curve batching bends: M-fill and launch
    amortization push it down until the batch-scaled VMEM feasibility
    bound prunes the fast blocks/strategies and it climbs back up (the
    cliff; the 3-D stars in ``BENCH_serve.json`` are the canonical case).
    Model-only: nothing is compiled.  ``plan_fn`` substitutes a custom
    planner (e.g. :meth:`repro.core.plan_cache.PlanCache.plan_only`, so a
    server's repeated queries reuse memoized plans); by default
    :func:`plan` runs with ``hw`` and ``plan_kwargs``.
    """
    if plan_fn is None:
        if hw is None:
            hw = _default_hw()

        def plan_fn(pb):
            return plan(pb, hw, **plan_kwargs)

    return {b: plan_fn(dataclasses.replace(problem, batch=b))
              .chosen().t_per_step
            for b in serving_buckets(max_batch)}


def max_profitable_batch(problem: StencilProblem, max_batch: int, hw=None, *,
                         rtol: float = 0.0,
                         plan_fn: Callable | None = None,
                         **plan_kwargs) -> int:
    """Largest serving bucket at or below the modelled per-state cost
    minimum — the admission-control cap for one shape group.

    The serving loop would otherwise round a full group up to
    ``max_batch`` and compile whatever the planner can still fit — past
    the VMEM cliff that is a strictly SLOWER executable per state (the
    batch-scaled residency bound prunes the fast blocks, or the inkernel
    strategy falls back to operator).  This query walks the
    :func:`batch_cost_curve` and returns the largest bucket whose cost is
    within ``rtol`` of the curve's minimum, so a server caps the group's
    bucket below the cliff instead of serving it.  Buckets larger than
    the returned cap are modelled as per-state regressions; smaller ones
    remain legal (a part-full group still rounds to the nearest bucket).
    """
    curve = batch_cost_curve(problem, max_batch, hw, plan_fn=plan_fn,
                             **plan_kwargs)
    best = min(curve.values())
    return max(b for b, t in curve.items() if t <= best * (1.0 + rtol))


# ---------------------------------------------------------------------------
# compile()
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledStencil:
    """A jit-ready executable for one ExecutionPlan.

    ``fn(x)`` advances ``plan.steps`` applications (already jitted for
    distributed plans; jit-safe — static schedule — for single-device
    plans).  ``global_fn`` is always traceable with ``jax.make_jaxpr``;
    ``step`` is the single shape-preserving step where one exists.
    """

    plan: ExecutionPlan
    fn: Callable
    global_fn: Callable
    step: Callable | None = None
    engine: StencilEngine | None = None
    stepper: Any | None = None

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.fn(x)


def _check_plan_input(x, grid: tuple[int, ...], nd: int, batch: int,
                      exact_rank: bool = False) -> None:
    """Shared shape gate of every compiled executable's entry point.

    ``exact_rank`` is set by the distributed wrapper, whose sharding spec
    has a fixed rank: there an unplanned extra leading axis must fail
    HERE with a clear error, not deep inside shard_map.  Single-device
    executables keep accepting ad-hoc leading axes at batch 1 (the
    engine cores are lead-polymorphic, as before this PR).
    """
    if tuple(x.shape[x.ndim - nd:]) != grid:
        raise ValueError(f"input spatial shape "
                         f"{tuple(x.shape[x.ndim - nd:])} != planned "
                         f"grid {grid}")
    lead = tuple(x.shape[:x.ndim - nd])
    if batch > 1 and lead != (batch,):
        raise ValueError(f"plan expects a leading batch axis of "
                         f"{batch}, got input shape {tuple(x.shape)}")
    if batch <= 1 and exact_rank and lead:
        raise ValueError(f"plan was compiled without a batch axis; got "
                         f"input shape {tuple(x.shape)} with leading axes "
                         f"{lead} (plan with batch={lead[0]} to batch)")


def compile_plan(eplan: ExecutionPlan, mesh=None) -> CompiledStencil:
    """Materialize an ExecutionPlan into an executable.

    Distributed plans (``sharding`` set) compile to the fused sharded
    stepper: ONE ``T*r``-deep halo exchange per fused chunk, then one
    sweep of the haloed block.  ``mesh`` defaults to rebuilding the
    recorded mesh shape from the available devices.
    """
    spec = eplan.spec
    boundary = eplan.boundary
    batch = eplan.batch
    if eplan.fuse_strategy not in FUSE_STRATEGIES:
        raise ValueError(f"plan carries unknown fuse strategy "
                         f"{eplan.fuse_strategy!r}; choose from "
                         f"{FUSE_STRATEGIES}")
    if eplan.sharding is not None:
        from repro.core.distributed import make_fused_distributed_stepper
        sh = eplan.sharding
        if mesh is None:
            from repro.launch.mesh import make_mesh
            mesh = make_mesh(sh["mesh_shape"], sh["mesh_axes"])
        if list(mesh.axis_names) != list(sh["mesh_axes"]) or \
                list(mesh.devices.shape) != list(sh["mesh_shape"]):
            raise ValueError(f"mesh {mesh.axis_names}{mesh.devices.shape} "
                             f"does not match the plan's {sh}")
        stepper = make_fused_distributed_stepper(
            spec, mesh, sh["grid_axes"], schedule=eplan.fuse_schedule,
            option=eplan.base_option,
            fused_option=eplan.option if eplan.fuse_depth > 1 else "auto",
            backend=eplan.backend, boundary=boundary, block=eplan.block,
            fuse_strategy=eplan.fuse_strategy,
            batch=batch if batch > 1 else None)

        def _checked(inner):
            # same clear shape errors the single-device fn raises, instead
            # of an opaque shard_map/in_shardings rank mismatch
            def f(x):
                _check_plan_input(x, eplan.grid, spec.ndim, batch,
                                  exact_rank=True)
                return inner(x)
            return f

        # fn routes through the stepper's __call__, not stepper.fn: the
        # host-side dist.* chaos wrapper lives there (a no-op global
        # read unless a FaultPlan is active; the jitted executable and
        # its ppermute census are identical either way)
        return CompiledStencil(plan=eplan, fn=_checked(stepper),
                               global_fn=_checked(stepper.global_fn),
                               stepper=stepper)

    eng = StencilEngine(spec, option=eplan.base_option, backend=eplan.backend,
                        block=eplan.block, boundary=boundary)
    strategy = eplan.fuse_strategy
    for t in set(eplan.fuse_schedule):
        if t > 1:
            if strategy == "inkernel":
                eng.inkernel_core(t)
            else:
                eng.fused_engine(t, option=eplan.option
                                 if t == eplan.fuse_depth else "auto")
    schedule = eplan.fuse_schedule
    grid = eplan.grid
    nd = spec.ndim

    def fn(x: jnp.ndarray) -> jnp.ndarray:
        _check_plan_input(x, grid, nd, batch)
        for t in schedule:
            x = eng._apply_chunk(x, t, strategy)
        return x

    step = eng.step_fn() if boundary != "valid" else None
    return CompiledStencil(plan=eplan, fn=fn, global_fn=fn, step=step,
                           engine=eng)
