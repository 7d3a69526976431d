"""Keyed plan + executable cache: plan once, compile once, serve forever.

Serving traffic repeats: the same (operator, grid shape, dtype, step
count, batch bucket) arrives over and over, and re-running ``plan()``
(cost-table enumeration) plus ``compile()`` (engine construction, kernel
planning, jit tracing) per request would dwarf the sweep itself.  This
module provides the memoization layer the serving loop
(:mod:`repro.launch.serve_stencil`) sits on:

  * :func:`cache_key` — ONE definition of executable identity: the spec's
    coefficient bytes, grid shape, dtype, boundary, steps, batch, the
    hardware model, the calibration record (by digest) and every planner
    pin.  Anything that can change the compiled core is in the key; two
    problems with equal keys are interchangeable executables.
  * :class:`PlanCache` — a bounded LRU mapping keys to
    :class:`CachedExecutable` (the frozen plan, the compiled stencil and
    a jitted entry point), with hit/miss/eviction counters.  A second
    identical request is a counter-visible hit that re-plans nothing and
    re-traces nothing (the jitted fn is reused, so ``fn._cache_size()``
    stays 1).

The cache is a plain in-process object — share one per server; create
fresh ones in tests.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import time
from collections import OrderedDict
from typing import Any, Callable, Mapping

import numpy as np

import jax

from repro.core.planner import (CompiledStencil, ExecutionPlan, PLAN_VERSION,
                                StencilProblem, _calibration_dict,
                                compile_plan, max_profitable_batch, plan)
from repro.runtime import chaos

__all__ = ["PlanCache", "CachedExecutable", "cache_key"]


def _spec_digest(spec) -> str:
    """Stable identity of a stencil operator: coefficient bytes + tag,
    plus the content-addressed scenario digest — two specs differing only
    in coefficient field or domain mask miss the cache separately."""
    c = np.ascontiguousarray(np.asarray(spec.gather_coeffs, np.float64))
    h = hashlib.sha1(c.tobytes())
    h.update(str(c.shape).encode())
    h.update(spec.shape.encode())
    h.update(spec.scenario_digest().encode())
    return h.hexdigest()[:16]


def _calibration_digest(calibration) -> str:
    if calibration is None:
        return "-"
    d = _calibration_dict(calibration)
    return hashlib.sha1(
        json.dumps(d, sort_keys=True).encode()).hexdigest()[:16]


def _freeze(v: Any):
    if isinstance(v, (list, tuple)):
        return tuple(_freeze(x) for x in v)
    if isinstance(v, Mapping):
        return tuple(sorted((k, _freeze(x)) for k, x in v.items()))
    return v


# Last-resort field list for hardware objects that expose neither
# dataclass fields nor a __dict__ (e.g. __slots__ shims); real specs are
# introspected so a newly added roofline field changes the key by itself.
_HW_FIELDS = ("name", "peak_flops_bf16", "hbm_bw", "ici_bw", "hbm_bytes",
              "launch_overhead_s")


def _hw_key(hw) -> tuple | None:
    """Hardware identity by PARAMETERS, not just name: two specs sharing a
    name but differing in any roofline constant (e.g. a
    ``launch_overhead_s`` override) must not alias executables.  The
    fields come from the object itself (dataclass fields, else
    ``vars()``), so a hardware model that GROWS a roofline field is a new
    identity without this module having to know the field's name."""
    if hw is None:
        return None
    if dataclasses.is_dataclass(hw) and not isinstance(hw, type):
        fields = tuple(f.name for f in dataclasses.fields(hw))
    else:
        d = getattr(hw, "__dict__", None)
        fields = tuple(sorted(d)) if d else _HW_FIELDS
    return tuple((f, getattr(hw, f, None)) for f in fields)


def _program_key(program) -> tuple | None:
    """Rollout-program identity slot of :func:`cache_key`.

    Accepts a :class:`repro.rollout.program.RolloutProgram` (duck-typed
    by its ``identity()``) or a pre-extracted identity tuple; ``None``
    (a plain sweep) stays ``None`` — so a rollout program and a plain
    sweep over the same :class:`StencilProblem` can NEVER collide, and
    two programs differing in any segment length, update-op content id
    or emit point key separately.
    """
    if program is None:
        return None
    ident = program.identity() if hasattr(program, "identity") else program
    return _freeze(ident)


def cache_key(problem: StencilProblem, *, hw=None, calibration=None,
              program=None, **plan_kwargs) -> tuple:
    """Executable identity of a problem + planning context.

    Everything that changes what ``compile(plan(problem, ...))`` builds is
    keyed: the operator (by coefficient digest), grid, dtype, boundary,
    steps, batch, mesh decomposition, the hardware model (by its roofline
    parameters, not just its name), the calibration record (by content
    digest — a re-measured record is a new executable), the rollout
    program identity (``program=`` — segment lengths, update-op ids and
    emit points; ``None`` for plain sweeps) and every planner pin
    (``fuse=``, ``backends=``, ``block=``, ``fuse_strategy=``, ...).
    PLAN_VERSION leads the tuple so a cache can never serve a
    stale-format plan across an upgrade.
    """
    sharding = None
    if problem.mesh is not None:
        sharding = (tuple(int(n) for n in problem.mesh.devices.shape),
                    tuple(problem.mesh.axis_names),
                    tuple(problem.grid_axes))
    return (
        PLAN_VERSION,
        _spec_digest(problem.spec),
        problem.grid,
        str(problem.dtype),
        problem.boundary,
        int(problem.steps),
        int(problem.batch),
        sharding,
        _program_key(program),
        _hw_key(hw),
        _calibration_digest(calibration),
        _freeze(plan_kwargs),
    )


@dataclasses.dataclass
class CachedExecutable:
    """One cache entry: the frozen decision record plus its executable.

    ``fn`` is the jitted entry point (already-jitted stepper for
    distributed plans); calling it with the same input shape never
    re-traces.  ``hits`` counts how many cache lookups this entry served
    after the compiling miss; ``calls`` counts SUCCESSFUL executions
    (the serving loop uses it to separate each executable's first
    trace+compile call from warm sweeps in its timing).

    Success accounting happens strictly AFTER device readiness: an async
    server launches with :meth:`dispatch` (which books nothing) and calls
    :meth:`mark_ready` once ``block_until_ready()`` returned without
    raising — so a deferred device error on the first call leaves the
    entry cold and the NEXT real first call's trace+compile time is still
    booked as compile, not warm, wall clock.  ``__call__`` is the
    synchronous convenience wrapping exactly that sequence.

    Per-entry timing hooks: ``compile_s`` accumulates the first
    successful call (trace + compile + sweep), ``wall_s`` every warm
    successful call — per-executable analogues of the serving loop's
    aggregate ``ServeStats`` counters.
    """

    key: tuple
    plan: ExecutionPlan
    compiled: CompiledStencil
    fn: Callable
    hits: int = 0
    calls: int = 0
    compile_s: float = 0.0   # first successful call (trace+compile+sweep)
    wall_s: float = 0.0      # warm successful calls

    @property
    def warm(self) -> bool:
        """Whether this executable has at least one SUCCESSFUL call."""
        return self.calls > 0

    def dispatch(self, x):
        """Launch without waiting or accounting (JAX async dispatch): the
        caller owns readiness and must :meth:`mark_ready` on success."""
        return self.fn(x)

    def mark_ready(self, wall_s: float = 0.0) -> bool:
        """Book one successful execution of ``wall_s`` seconds; returns
        whether the entry was already warm BEFORE this call (i.e. whether
        ``wall_s`` was booked as warm rather than compile time)."""
        warm = self.calls > 0
        if warm:
            self.wall_s += wall_s
        else:
            self.compile_s += wall_s
        self.calls += 1
        return warm

    def __call__(self, x):
        t0 = time.perf_counter()
        out = self.dispatch(x)
        # pytree-safe: rollout-program entries return (final, emits)
        jax.block_until_ready(out)
        self.mark_ready(time.perf_counter() - t0)
        return out


class PlanCache:
    """Bounded LRU of compiled stencil executables with observable counters.

    ``get(problem, **plan_kwargs)`` returns a :class:`CachedExecutable`,
    planning + compiling + jitting only on a miss.  ``maxsize`` bounds the
    entry count (least-recently-used plans are evicted — their jit caches
    go with them, so a bounded serving process cannot accumulate
    executables without bound).
    """

    def __init__(self, maxsize: int = 32, hw=None):
        if maxsize < 1:
            raise ValueError("maxsize >= 1")
        self.maxsize = int(maxsize)
        self._hw = hw
        self._entries: OrderedDict[tuple, CachedExecutable] = OrderedDict()
        # plan-without-compile memo (admission-control queries): bounded
        # separately — plans are small frozen records, executables are not
        self._plans: OrderedDict[tuple, ExecutionPlan] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: tuple) -> bool:
        return key in self._entries

    @property
    def hw(self):
        """The hardware model every lookup plans against (None = default)."""
        return self._hw

    def plan_only(self, problem: StencilProblem, *, calibration=None,
                  **plan_kwargs) -> ExecutionPlan:
        """The frozen plan for ``problem`` WITHOUT compiling anything.

        Memoized under the same :func:`cache_key` as :meth:`get` and
        reused by it, so a model-only query (the admission-control
        bucket-cliff walk) is never planning work thrown away: if the
        server later compiles the same problem, the miss skips straight
        to compile.  Does not touch the executable hit/miss counters.
        """
        key = cache_key(problem, hw=self._hw, calibration=calibration,
                        **plan_kwargs)
        entry = self._entries.get(key)
        if entry is not None:
            return entry.plan
        p = self._plans.get(key)
        if p is None:
            p = plan(problem, self._hw, calibration=calibration,
                     **plan_kwargs)
            self._plans[key] = p
            while len(self._plans) > 4 * self.maxsize:
                self._plans.popitem(last=False)
        else:
            self._plans.move_to_end(key)
        return p

    def bucket_cap(self, problem: StencilProblem, max_batch: int, *,
                   calibration=None, rtol: float = 0.0,
                   **plan_kwargs) -> int:
        """:func:`repro.core.planner.max_profitable_batch` through this
        cache's plan memo: the largest serving bucket below the modelled
        VMEM cliff for ``problem``'s shape group (its ``batch`` is
        ignored), with every walked plan retained for later compiles."""
        return max_profitable_batch(
            problem, max_batch, self._hw, rtol=rtol,
            plan_fn=lambda pb: self.plan_only(pb, calibration=calibration,
                                              **plan_kwargs))

    def get(self, problem: StencilProblem, *, calibration=None,
            mesh=None, **plan_kwargs) -> CachedExecutable:
        """The compiled executable for ``problem``, memoized.

        ``plan_kwargs`` pass through to :func:`repro.core.planner.plan`
        (and join the key); ``mesh`` is only needed to materialize a
        distributed plan's stepper and is NOT part of the key beyond the
        problem's own mesh decomposition.
        """
        key = cache_key(problem, hw=self._hw, calibration=calibration,
                        **plan_kwargs)
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            self.hits += 1
            entry.hits += 1
            return entry
        self.misses += 1
        # a prior plan_only() query (admission control) already planned
        # this exact key: reuse its frozen record, compile only
        p = self._plans.pop(key, None)
        if p is None:
            p = plan(problem, self._hw, calibration=calibration,
                     **plan_kwargs)
        # fault site: an injected compile failure leaves no cache entry
        # behind (the miss was already counted — honest accounting)
        chaos.fire("cache.compile", backend=p.backend,
                   batch=int(problem.batch))
        compiled = compile_plan(p, mesh=mesh)
        # distributed steppers are already jitted; jit single-device fns
        # here so a repeated request cannot re-trace either
        fn = compiled.fn if p.sharding is not None else jax.jit(compiled.fn)
        entry = CachedExecutable(key=key, plan=p, compiled=compiled, fn=fn)
        self._entries[key] = entry
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1
        return entry

    def get_program(self, program, *, calibration=None, mesh=None,
                    **plan_kwargs) -> CachedExecutable:
        """The compiled executable for a whole rollout program, memoized
        as ONE entry.

        ``program`` is a :class:`repro.rollout.program.RolloutProgram`;
        the entry's ``fn(x)`` runs every segment and returns
        ``(final state, tuple of emitted states)`` — a pytree, which
        :meth:`CachedExecutable.__call__`/servers must block on with
        ``jax.block_until_ready``.  Keyed by the problem (at the
        program's total step count) PLUS the program identity
        (:func:`_program_key`), so it can never alias a plain sweep;
        per-segment planning routes through :meth:`plan_only`'s memo, so
        programs sharing segment shapes share cost tables.  The entry's
        ``plan`` is the :class:`repro.rollout.planning.RolloutPlan`.

        Mesh-sharded programs key like distributed sweeps — the mesh
        SHAPE is part of :func:`cache_key` via the problem's sharding
        tuple (a reshard is a different executable), while ``mesh``
        itself only materializes the steppers, exactly as in :meth:`get`.
        """
        from repro.rollout.executor import compile_program
        from repro.rollout.planning import plan_program
        key = cache_key(dataclasses.replace(program.problem,
                                            steps=program.total_steps),
                        hw=self._hw, calibration=calibration,
                        program=program, **plan_kwargs)
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
            self.hits += 1
            entry.hits += 1
            return entry
        self.misses += 1
        rplan = plan_program(program, self._hw, cache=self,
                             calibration=calibration, **plan_kwargs)
        chaos.fire("cache.compile",
                   backend=rplan.segment_plans[0].backend,
                   batch=int(program.problem.batch))
        compiled = compile_program(rplan, mesh=mesh)

        def fn(x):
            # per-segment sweeps/updates are already jitted inside
            # compile_program; the program loop is host-side control flow
            res = compiled.run(x)
            return res.final, tuple(a for _, a in res.emits)

        entry = CachedExecutable(key=key, plan=rplan, compiled=compiled,
                                 fn=fn)
        self._entries[key] = entry
        while len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
            self.evictions += 1
        return entry

    def stats(self) -> dict:
        return {"size": len(self._entries), "maxsize": self.maxsize,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions, "plans": len(self._plans)}

    def clear(self) -> None:
        self._entries.clear()
        self._plans.clear()
