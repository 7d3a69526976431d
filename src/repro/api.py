"""Public facade: ``StencilProblem -> plan() -> ExecutionPlan -> compile()``.

    from repro import api

    problem = api.StencilProblem(api.star(2, 2), grid=(256, 256),
                                 boundary="periodic", steps=32)
    p = api.plan(problem)           # frozen, JSON-serializable decisions
    print(p.explain())              # per-decision modelled roofline costs
    run = api.compile(p)            # jit-ready executable
    y = run(x)

The planner autotunes the output tile (``candidate_blocks`` enumerates
MXU-aligned blocks and every candidate row is scored per block), and its
analytic cost table can be calibrated against real compiled executables:

    record = api.calibrate(problem, backends=["jnp"])   # measure top-K
    p = api.plan(problem, calibration=record)           # re-rank measured

Distributed: give the problem a mesh and per-axis mesh names and the
compiled stepper exchanges a single ``T*r``-deep halo once per fused chunk
(DESIGN.md §Planner).  Third-party kernels plug in through
:func:`register_backend` and are scored by the same cost model; see
DESIGN.md §Autotune for the block-search space and the calibration record
schema, and README.md for a runnable tour of this module.

Serving: ``StencilProblem(batch=B)`` makes the batch a planner-visible
dimension (folded into the kernels' MXU contractions, priced per STATE by
the cost model); :class:`PlanCache` memoizes compiled executables by
everything that changes them, and :class:`StencilServer` buckets a
variable-size request stream onto both (DESIGN.md §Batch):

    server = api.StencilServer(api.box(2, 1), steps=8, max_batch=8)
    evolved = server.serve(list_of_states)

Varying coefficients & masked domains (README §Varying coefficients,
DESIGN.md §Scenarios): ``spec.with_field(a, domain_mask=m)`` attaches a
per-point coefficient field and/or boolean domain mask to the spec — a
first-class plan dimension (content-addressed cache identity, aux-band
pricing, fusion-legality fallbacks) executed as an elementwise scale on
the same banded-Toeplitz contractions; seeded generators
:func:`random_coeff_field` / :func:`random_domain_mask` are re-exported
here.

Rollout programs (README §Rollout, DESIGN.md §Rollout): interleave fused
sweeps with registered pointwise update operators (forcing terms,
observation-style nudging, user callables) as one planned, cached,
checkpointable executable:

    program = api.RolloutProgram(problem, [
        api.Segment(8, api.UpdateOp("source", {"scale": 0.1}), emit=True),
        api.Segment(8, api.UpdateOp("nudge", {"gain": 0.2})),
        api.Segment(16)])
    rplan = api.plan_program(program)     # per-segment fuse decisions
    result = api.compile_program(rplan).run(x)   # final + emitted states
    api.run_checkpointed(...)             # restartable, bit-exact resume

Robustness (README §Chaos, DESIGN.md §Robustness): the supervision
primitives (:class:`RestartPolicy`, :class:`HeartbeatMonitor`,
:func:`supervised`) drive both the serving scheduler's per-group retry
budgets and the checkpointed rollout driver, and a seeded
:class:`FaultPlan` injects deterministic failures at named sites to
prove recovery end to end:

    plan = api.FaultPlan(seed=0).rule("serve.settle", rate=0.3)
    with plan:                            # every result still bit-exact
        outs = server.serve(states)
"""
from __future__ import annotations

from repro.core.engine import (Backend, StencilEngine, backend_names,
                               choose_cover, default_block, get_backend,
                               legal_covers, register_backend)
from repro.core.plan_cache import CachedExecutable, PlanCache, cache_key
from repro.core.planner import (CandidateCost, CompiledStencil, ExecutionPlan,
                                FUSE_STRATEGIES, PLAN_VERSION, StencilProblem,
                                batch_cost_curve, best_block, candidate_blocks,
                                candidate_cost, compile_plan,
                                max_profitable_batch, plan, serving_buckets)
from repro.core.stencil_spec import (PAPER_SUITE, StencilSpec, box, diagonal,
                                     from_gather_coeffs, random_coeff_field,
                                     random_domain_mask, star)
from repro.launch.calibrate import (CalibrationRecord, CandidateMeasurement,
                                    calibrate, measure_candidate)
from repro.launch.serve_stencil import (SERVE_SPANS, RequestShed,
                                        ServeStats, StencilServer)
from repro.rollout import (CompiledRollout, RolloutPlan, RolloutProgram,
                           RolloutResult, Segment, UpdateOp, compile_program,
                           plan_program, register_update_op, run_checkpointed,
                           update_op_names)
from repro.runtime.chaos import FAULT_SITES, FaultError, FaultPlan, FaultRule
from repro.runtime.fault_tolerance import (HeartbeatMonitor, RestartPolicy,
                                           StepTimeout, supervised)

compile = compile_plan  # noqa: A001 - the facade verb (shadows the builtin
#                         inside this namespace only, by design)

__all__ = [
    "StencilProblem", "ExecutionPlan", "CandidateCost", "CompiledStencil",
    "plan", "compile", "compile_plan", "candidate_cost", "candidate_blocks",
    "best_block", "batch_cost_curve", "max_profitable_batch",
    "serving_buckets", "FUSE_STRATEGIES", "PLAN_VERSION",
    "CalibrationRecord", "CandidateMeasurement", "calibrate",
    "measure_candidate",
    "PlanCache", "CachedExecutable", "cache_key",
    "StencilServer", "ServeStats", "RequestShed", "SERVE_SPANS",
    "FaultPlan", "FaultRule", "FaultError", "FAULT_SITES",
    "RestartPolicy", "HeartbeatMonitor", "StepTimeout", "supervised",
    "RolloutProgram", "Segment", "UpdateOp", "RolloutPlan", "RolloutResult",
    "CompiledRollout", "plan_program", "compile_program", "run_checkpointed",
    "register_update_op", "update_op_names",
    "StencilEngine", "Backend", "register_backend", "get_backend",
    "backend_names", "choose_cover", "legal_covers", "default_block",
    "StencilSpec", "box", "star", "diagonal", "from_gather_coeffs",
    "random_coeff_field", "random_domain_mask", "PAPER_SUITE",
]
