"""Measured-cost calibration for the planner (DESIGN.md §Autotune).

``plan()`` ranks (cover x backend x fuse x block) candidates with a purely
analytic roofline; this module confronts that model with real compiled
executables and feeds the discrepancy back:

  * :func:`measure_candidate` compiles ONE candidate of a problem (the
    fused chunk at its depth/cover/backend/block), then reads the
    loop-aware HLO cost analysis (``launch.hlo_analysis.analyze_hlo`` —
    exact dot FLOPs from shapes, fusion-granularity HBM traffic) and
    optionally wall-clock timing off the compiled executable.
  * :func:`calibrate` measures a plan's top-K candidates and freezes the
    per-backend ``measured/modelled`` ratios into a
    :class:`CalibrationRecord` — a frozen, JSON-round-trippable artifact.
  * ``plan(problem, calibration=record)`` then re-ranks the cost table
    with the measured factors: the compute factor divides the backend's
    modelled ``mxu_efficiency`` (``Backend.effective_efficiency``), the
    traffic factor scales ``t_traffic``.

The record is the shared serialization for every measured-cost path:
``dryrun --stencil-calibrate`` emits the same JSON shape, and
``plan_report --calibration record.json`` renders a calibrated report.

    PYTHONPATH=src python -m repro.launch.dryrun --stencil-calibrate
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.engine import StencilEngine
from repro.core.planner import (StencilProblem, candidate_cost, plan,
                                factor_key as _factor_key)
from repro.core.stencil_spec import PAPER_SUITE
from repro.launch.hlo_analysis import analyze_hlo

__all__ = ["CandidateMeasurement", "CalibrationRecord", "measure_candidate",
           "calibrate", "calibrate_suite", "factor_key",
           "CALIBRATION_VERSION"]

CALIBRATION_VERSION = 2

# THE key format lives beside its reader (planner._calib_factor); this
# module only re-exports it for record construction.
factor_key = _factor_key


@dataclasses.dataclass(frozen=True)
class CandidateMeasurement:
    """Modelled-vs-measured costs of one compiled candidate.

    ``modelled_*`` are the planner's raw roofline terms (per fused sweep
    over the local grid, from :func:`repro.core.planner.candidate_cost`);
    ``measured_*`` come from the compiled executable's HLO (loop-corrected
    dot FLOPs and fusion-granularity HBM traffic).  ``wall_s`` is the
    median wall-clock of the compiled chunk on THIS host (None unless
    timing was requested — on a CPU container it measures XLA-CPU, so only
    its ranking, never its magnitude, is comparable to the TPU model).
    ``strategy`` records which temporal execution was compiled ("operator"
    fused-operator chunk | "inkernel" VMEM-resident multi-step kernel).
    """
    depth: int
    option: str
    backend: str
    block: tuple[int, ...]
    modelled_flops: float
    modelled_bytes: float
    measured_flops: float
    measured_bytes: float
    wall_s: float | None = None
    strategy: str = "operator"


@dataclasses.dataclass(frozen=True)
class CalibrationRecord:
    """Frozen per-(backend, strategy) efficiency factors, with evidence.

    Factor tables are keyed by :func:`factor_key` — the bare backend name
    for operator-strategy measurements, ``"backend:inkernel"`` for
    in-kernel ones.  ``compute[key]`` is the measured/modelled MXU-flop
    ratio (median over that key's measurements): the planner divides the
    backend's modelled ``mxu_efficiency`` by it.  ``traffic[key]`` is the
    measured/modelled HBM-byte ratio: the planner multiplies ``t_traffic``
    by it.  Factors are strictly positive, so calibration is a monotone
    per-key rescaling — it can re-rank backends against each other but
    never ranks a candidate above one that strictly dominates it on every
    raw term within the same (backend, strategy) (regression-tested in
    ``tests/test_calibrate.py``).

    JSON-round-trippable by construction:
    ``CalibrationRecord.from_json(r.to_json()) == r``.
    """
    version: int
    hw: str
    problem: dict                 # what was measured (suite cell metadata)
    compute: dict[str, float]     # backend -> measured/modelled flops ratio
    traffic: dict[str, float]     # backend -> measured/modelled bytes ratio
    measurements: tuple[CandidateMeasurement, ...]

    # -- construction ------------------------------------------------------
    @classmethod
    def from_measurements(cls, hw: str, problem: dict,
                          measurements: Sequence[CandidateMeasurement]
                          ) -> "CalibrationRecord":
        """Pool measurements into per-(backend, strategy) median factors."""
        compute: dict[str, float] = {}
        traffic: dict[str, float] = {}
        keys = sorted({factor_key(m.backend, m.strategy)
                       for m in measurements})
        for key in keys:
            ms = [m for m in measurements
                  if factor_key(m.backend, m.strategy) == key]
            fl = [m.measured_flops / m.modelled_flops for m in ms
                  if m.modelled_flops > 0 and m.measured_flops > 0]
            by = [m.measured_bytes / m.modelled_bytes for m in ms
                  if m.modelled_bytes > 0 and m.measured_bytes > 0]
            compute[key] = float(np.median(fl)) if fl else 1.0
            traffic[key] = float(np.median(by)) if by else 1.0
        return cls(version=CALIBRATION_VERSION, hw=hw, problem=dict(problem),
                   compute=compute, traffic=traffic,
                   measurements=tuple(measurements))

    # -- serialization (the calibrate/dryrun shared serializer) ------------
    def to_json(self, indent: int | None = None) -> str:
        d = dataclasses.asdict(self)
        d["measurements"] = [dict(dataclasses.asdict(m), block=list(m.block))
                             for m in self.measurements]
        return json.dumps(d, indent=indent)

    @classmethod
    def from_json(cls, text: str) -> "CalibrationRecord":
        d = json.loads(text)
        if d.get("version") != CALIBRATION_VERSION:
            raise ValueError(
                f"calibration version {d.get('version')!r} does not match "
                f"this code's CALIBRATION_VERSION={CALIBRATION_VERSION}; "
                f"re-run the calibration pass")
        d["measurements"] = tuple(
            CandidateMeasurement(**dict(m, block=tuple(m["block"])))
            for m in d["measurements"])
        return cls(**d)


# ---------------------------------------------------------------------------
# Measurement
# ---------------------------------------------------------------------------

def measure_candidate(problem: StencilProblem, depth: int, option: str,
                      backend: str, block: tuple[int, ...], *,
                      wall: bool = False,
                      repeats: int = 3,
                      base_option: str | None = None,
                      strategy: str = "operator") -> CandidateMeasurement:
    """Compile one candidate's fused chunk and read its measured costs.

    The executable is exactly what ``compile_plan`` would run per chunk:
    the engine's ``_apply_chunk`` at ``depth`` with ``strategy`` (fused
    operator re-covered with ``option``, or the in-kernel multi-step core
    over the base cover ``option``; boundary handling included), jitted
    over the device-local grid.  Measured FLOPs/bytes come from the
    loop-aware HLO analysis of the compiled module — the same analysis
    ``launch.dryrun`` applies to the production cells.
    """
    spec = problem.spec
    local_grid = problem.local_grid()
    # the base engine's cover must match compile_plan's (it prices the
    # zero-boundary strip fixups at depth>1, and for the in-kernel strategy
    # it IS the per-step cover): the pinned base_option if the plan had
    # one, the candidate's own cover for in-kernel/depth-1 rows, else the
    # same choose_cover default compile_plan uses
    if depth == 1 or strategy == "inkernel":
        base_opt = option
    else:
        base_opt = base_option or "auto"
    eng = StencilEngine(spec, option=base_opt,
                        backend=backend, block=tuple(block),
                        boundary=problem.boundary)
    if depth > 1:
        if strategy == "inkernel":
            eng.inkernel_core(depth)
        else:
            eng.fused_engine(depth, option=option)

    fn = jax.jit(lambda x: eng._apply_chunk(x, depth, strategy))
    x = jnp.zeros(local_grid, jnp.dtype(problem.dtype))
    compiled = fn.lower(x).compile()
    hlo = analyze_hlo(compiled.as_text())

    wall_s = None
    if wall:
        compiled(x).block_until_ready()
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            compiled(x).block_until_ready()
            ts.append(time.perf_counter() - t0)
        wall_s = float(np.median(ts))

    modelled = candidate_cost(problem, depth, option, backend, block=block,
                              base_option=base_option, strategy=strategy)
    return CandidateMeasurement(
        depth=depth, option=option, backend=backend, block=tuple(block),
        modelled_flops=float(modelled.mxu_flops),
        modelled_bytes=float(modelled.hbm_bytes),
        measured_flops=float(hlo.dot_flops),
        measured_bytes=float(hlo.traffic_bytes),
        wall_s=wall_s, strategy=strategy)


def calibrate(problem: StencilProblem, hw=None, *, top_k: int = 3,
              wall: bool = False,
              **plan_kwargs) -> CalibrationRecord:
    """Measure a problem's top-K planned candidates into a record.

    ``plan_kwargs`` pass through to :func:`repro.core.planner.plan`
    (``backends=``, ``option=``, ``fuse=``, ...), so the measured set can
    be restricted to the backends worth compiling on this host.  The
    resulting record feeds straight back:
    ``plan(problem, calibration=calibrate(problem, ...))``.
    """
    p = plan(problem, hw, **plan_kwargs)
    ranked = p.ranked()[:max(1, top_k)]
    measurements = [
        measure_candidate(problem, c.depth, c.option, c.backend, c.block,
                          wall=wall,
                          base_option=plan_kwargs.get("option"),
                          strategy=c.strategy)
        for c in ranked]
    return CalibrationRecord.from_measurements(
        p.hw["name"], problem.to_dict(), measurements)


def calibrate_suite(names: Sequence[str] = ("box2d_r1", "star2d_r2"),
                    grid: tuple[int, ...] = (96, 96), steps: int = 8,
                    backends: Sequence[str] = ("jnp", "codegen"),
                    hw=None, top_k: int = 2,
                    wall: bool = False) -> CalibrationRecord:
    """One pooled record over a small PAPER_SUITE subset.

    This is what ``dryrun --stencil-calibrate`` emits: a single
    CalibrationRecord whose factors pool every (cell x candidate)
    measurement, serialized by the same ``to_json`` the API uses.
    """
    suite = PAPER_SUITE()
    measurements: list[CandidateMeasurement] = []
    hw_name = None
    for name in names:
        spec = suite[name]
        # per-cell grid: truncate to the spec's dimensionality, or extend
        # with the last extent (e.g. (96, 96) -> (96, 96, 96) for 3-D)
        cell_grid = (grid[:spec.ndim] if spec.ndim <= len(grid)
                     else grid + (grid[-1],) * (spec.ndim - len(grid)))
        problem = StencilProblem(spec, cell_grid,
                                 boundary="periodic", steps=steps)
        p = plan(problem, hw, backends=list(backends))
        hw_name = p.hw["name"]
        for c in p.ranked()[:max(1, top_k)]:
            measurements.append(
                measure_candidate(problem, c.depth, c.option, c.backend,
                                  c.block, wall=wall, strategy=c.strategy))
    meta = {"suite": list(names), "grid": list(grid), "steps": int(steps),
            "backends": list(backends)}
    return CalibrationRecord.from_measurements(hw_name or "", meta,
                                               measurements)
