"""Compiled rollout execution: streaming, checkpointed, restartable.

:func:`compile_program` lowers a :class:`~repro.rollout.planning
.RolloutPlan` into a :class:`CompiledRollout`: one jitted fused sweep per
DISTINCT segment plan (segments sharing a plan share the executable and
its jit cache) plus one jitted update fn per distinct (op, shape).  The
update runs as its own tiny pointwise kernel AFTER the segment's fused
sweep — it is a fusion barrier by construction, so the sweep executable
is byte-identical to the single-sweep path and inherits its exactness
guarantees; streaming an emit point costs nothing extra (the post-update
state is already materialized).

:func:`run_checkpointed` is the production driver the seed's idle
runtime modules were waiting for: segment-boundary checkpoints through
:class:`~repro.checkpoint.checkpointer.CheckpointManager` (atomic
rename, ``keep_last`` retention), resume-from-latest that is BIT-exact
vs an uninterrupted run (float32 states round-trip ``.npz`` exactly, and
re-running a segment from its checkpointed start state is deterministic),
and per-segment :class:`~repro.runtime.fault_tolerance.HeartbeatMonitor`
/ :class:`~repro.runtime.fault_tolerance.RestartPolicy` guards: a failed
or timed-out segment re-runs from its start state after bounded backoff.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Callable, Sequence

import msgpack
import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.checkpointer import CheckpointManager, restore_checkpoint
from repro.core.planner import compile_plan
from repro.rollout.planning import RolloutPlan, plan_program
from repro.rollout.program import (RolloutProgram, build_update,
                                   segment_out_grid)
from repro.runtime import chaos
from repro.runtime.chaos import FaultError
from repro.runtime.fault_tolerance import supervised

__all__ = ["CompiledRollout", "RolloutResult", "compile_program",
           "run_checkpointed", "shrink_mesh"]


@dataclasses.dataclass(frozen=True)
class RolloutResult:
    """Final state plus every emitted intermediate, in program order.

    ``emits`` pairs each emitting segment's CUMULATIVE step count with
    its post-update state.

    ``attempts[i]`` counts how many times segment ``i`` was DISPATCHED
    (1 = clean first try; 0 = skipped by a checkpoint resume) and
    ``recovered[i]`` flags segments whose surviving state came from a
    retry (attempt > 1) — the previously-unrecorded fact of WHICH
    attempt produced each checkpoint.  ``resharded`` counts mesh-shrink
    recoveries (``run_checkpointed`` rebuilding the distributed stepper
    on fewer devices after a ``dist.*`` fault exhausted a segment's
    retry budget).
    """

    final: Any
    emits: tuple[tuple[int, Any], ...] = ()
    attempts: tuple[int, ...] = ()
    recovered: tuple[int, ...] = ()
    resharded: int = 0

    def emit_dict(self) -> dict[int, Any]:
        return dict(self.emits)


@dataclasses.dataclass
class CompiledRollout:
    """Executable form of one rollout program.

    ``run(x)`` drives the whole program; :meth:`stream` yields after
    every segment (the serving loop's drain unit); :meth:`run_segment`
    is one segment's sweep+update — the retry unit
    :func:`run_checkpointed` guards.
    """

    plan: RolloutPlan
    program: RolloutProgram
    sweeps: tuple[Callable, ...]          # one jitted fused sweep per segment
    updates: tuple[Callable | None, ...]  # jitted pointwise update or None
    mesh: Any = None                      # live Mesh of distributed sweeps

    def run_segment(self, i: int, x):
        """Advance one segment: fused sweep, then the update op."""
        y = self.sweeps[i](x)
        up = self.updates[i]
        if up is not None:
            y = up(y)
            chaos.fire("rollout.update", segment=int(i))
        return y

    def stream(self, x, start_segment: int = 0):
        """Yield ``(segment index, cumulative step, state)`` after every
        segment — emit filtering is the caller's (states stream without
        re-entering the fused sweep)."""
        segs = self.program.segments
        t = sum(s.steps for s in segs[:start_segment])
        for i in range(start_segment, len(segs)):
            x = self.run_segment(i, x)
            t += segs[i].steps
            yield i, t, x

    def run(self, x, start_segment: int = 0) -> RolloutResult:
        emits = []
        for i, t, x in self.stream(x, start_segment):
            if self.program.segments[i].emit:
                emits.append((t, x))
        return RolloutResult(final=x, emits=tuple(emits))

    def __call__(self, x) -> RolloutResult:
        return self.run(x)


def compile_program(rplan: RolloutPlan | RolloutProgram, *,
                    hw=None, mesh=None,
                    **plan_kwargs) -> CompiledRollout:
    """Materialize a rollout plan (planning first if given a program).

    Distinct segments sharing an identical plan share ONE jitted sweep
    (and therefore one trace/compile); updates dedupe by (op identity,
    output shape).  The per-segment sweep is exactly the single-sweep
    ``compile_plan`` executable, so everything proven about fused sweeps
    (bit-exactness per strategy, boundary handling) holds per segment.

    Mesh-sharded programs (the problem carries a ``mesh``, or the plan's
    segments record a ``sharding``) compile each segment to the fused
    distributed stepper — one ``t*r``-deep exchange per fused chunk,
    exactly the single-sweep executable again.  ``mesh`` binds the live
    device mesh (default: rebuilt from the recorded shape, as in
    ``compile_plan``); it never enters the plan or the program digest.
    """
    if isinstance(rplan, RolloutProgram):
        rplan = plan_program(rplan, hw, **plan_kwargs)
    program = rplan.program_obj()
    sweep_by_plan: dict[str, Callable] = {}
    update_by_key: dict[tuple, Callable] = {}
    sweeps, updates = [], []
    for i, seg in enumerate(program.segments):
        p = rplan.segment_plans[i]
        pj = p.to_json()
        fn = sweep_by_plan.get(pj)
        if fn is None:
            cp = compile_plan(p, mesh=mesh)
            # distributed sweeps are already jitted inside the stepper
            # (and their host-side chaos wrapper must NOT be traced);
            # single-device fns pick up their jit here as before
            fn = cp.fn if p.sharding is not None else jax.jit(cp.fn)
            if p.sharding is not None and mesh is None:
                mesh = cp.stepper.mesh
            sweep_by_plan[pj] = fn
        sweeps.append(fn)
        if seg.update is None:
            updates.append(None)
            continue
        pb = program.segment_problem(i)
        out_grid = segment_out_grid(pb)
        ukey = (seg.update.update_id, out_grid)
        ufn = update_by_key.get(ukey)
        if ufn is None:
            ufn = jax.jit(build_update(seg.update, pb, out_grid))
            update_by_key[ukey] = ufn
        updates.append(ufn)
    return CompiledRollout(plan=rplan, program=program,
                           sweeps=tuple(sweeps), updates=tuple(updates),
                           mesh=mesh)


# ---------------------------------------------------------------------------
# Reshard-on-failure: shrink the mesh, keep the plan
# ---------------------------------------------------------------------------

def _is_dist_fault(err: BaseException | None) -> bool:
    """Did this failure originate at a ``dist.*`` chaos site (an injected
    mesh fault — the class of error a smaller mesh survives)?"""
    while err is not None:
        if isinstance(err, FaultError) and err.site.startswith("dist."):
            return True
        err = err.__cause__
    return False


def shrink_mesh(mesh: Mesh) -> Mesh:
    """The next-smaller mesh after losing devices: halve the largest
    axis (same axis names, same GLOBAL grid — local blocks double).

    Keeps the leading surviving devices of the old mesh's device array;
    an even axis halves (preserving grid divisibility: any grid an
    N-way axis divided, N/2 divides too), an odd one collapses to 1.
    Raises when the mesh is already 1x...x1.
    """
    shape = list(mesh.devices.shape)
    sizes = [(n, j) for j, n in enumerate(shape) if n > 1]
    if not sizes:
        raise ValueError(f"mesh {tuple(shape)} cannot shrink further")
    _, j = max(sizes)
    shape[j] = shape[j] // 2 if shape[j] % 2 == 0 else 1
    survivors = mesh.devices.reshape(-1)[: int(np.prod(shape))]
    return Mesh(survivors.reshape(shape), mesh.axis_names)


def _reshard_compiled(compiled: CompiledRollout,
                      new_mesh: Mesh) -> CompiledRollout:
    """Rebuild every distributed sweep on ``new_mesh``, REUSING the
    frozen segment plans (same fuse schedule / backend / block — only
    the recorded mesh shape changes), so the resumed numerics are the
    already-proven fused-sweep executables on bigger local blocks."""
    new_shape = [int(n) for n in new_mesh.devices.shape]
    plans = tuple(
        dataclasses.replace(p, sharding={**p.sharding,
                                         "mesh_shape": new_shape})
        if p.sharding is not None else p
        for p in compiled.plan.segment_plans)
    rplan = dataclasses.replace(compiled.plan, segment_plans=plans)
    return compile_program(rplan, mesh=new_mesh)


def _state_sharding(compiled: CompiledRollout) -> NamedSharding | None:
    """The NamedSharding rollout states live under (None if the program
    is single-device)."""
    if compiled.mesh is None:
        return None
    p0 = next((p for p in compiled.plan.segment_plans
               if p.sharding is not None), None)
    if p0 is None:
        return None
    lead = [None] if p0.batch > 1 else []
    axes = [a if a else None for a in p0.sharding["grid_axes"]]
    return NamedSharding(compiled.mesh, P(*(lead + axes)))


# ---------------------------------------------------------------------------
# Checkpointed, fault-tolerant driving
# ---------------------------------------------------------------------------

def _checkpoint_tree(state, emits: Sequence[tuple[int, Any]]) -> dict:
    return {"state": state,
            "emits": {f"{t:08d}": a for t, a in emits}}


def _manifest_target(directory: str, step: int) -> dict:
    """Zero-leaf target tree matching a checkpoint's manifest — restore
    needs a structural template, and the emit count varies per step."""
    path = os.path.join(directory, f"step_{step:08d}", "manifest.msgpack")
    with open(path, "rb") as f:
        manifest = msgpack.unpackb(f.read())
    tree: dict = {}
    for entry in manifest["leaves"]:
        parts = entry["key"].split("/")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = np.zeros((), np.dtype(entry["dtype"]))
    return tree


def run_checkpointed(compiled: CompiledRollout, x, *,
                     directory: str | None = None,
                     keep_last: int | None = 3,
                     monitor=None,
                     restart=None,
                     fault_injector: Callable | None = None,
                     resume: bool = True) -> RolloutResult:
    """Drive a compiled rollout with checkpoints and restart guards.

    After every segment the post-update state (plus all emits so far)
    is checkpointed synchronously to ``directory`` under the atomic
    ``step_XXXXXXXX`` layout, retaining the last ``keep_last``; a process
    killed mid-program re-invokes this function and (``resume=True``)
    continues from the latest checkpoint — bit-exact vs an uninterrupted
    run, guarded by the program's content digest.

    ``monitor`` (:class:`HeartbeatMonitor`) brackets each segment as one
    heartbeat step — a ``hard_timeout_s`` overrun raises
    :class:`StepTimeout` into the retry path.  ``restart``
    (:class:`RestartPolicy`) converts a failed segment into
    sleep-backoff-and-re-run-from-segment-start; without one, failures
    propagate (with checkpoints intact for the next attempt).  Both run
    through the shared :func:`repro.runtime.fault_tolerance.supervised`
    loop — the same primitives the serving scheduler's per-group retry
    budgets use.  ``fault_injector(segment, attempt)`` runs after each
    segment's dispatch and may raise — the legacy test hook; the chaos
    sites ``rollout.segment`` / ``checkpoint.write`` /
    ``checkpoint.read`` (:mod:`repro.runtime.chaos`) are the seeded
    equivalent.

    Resume walks the retained checkpoints NEWEST-FIRST: a torn or
    corrupt latest checkpoint (truncated manifest, unreadable shards —
    e.g. a chaos-injected torn write, or a single torn SHARD caught by
    its manifest digest) is skipped in favor of the previous retained
    one (the ``keep_last`` window exists precisely so a bad latest is
    not fatal); only a checkpoint that restores cleanly but belongs to
    a DIFFERENT program raises.

    Mesh-sharded programs add a LAST rung under the same supervision:
    when a ``dist.*`` fault (an injected mesh failure — lost device,
    failed chunk dispatch, corrupted exchange) exhausts a segment's
    retry budget, the executor RESHARDS instead of dying — it rebuilds
    every distributed sweep on the next-smaller mesh (same global grid,
    same frozen per-segment plans, bigger local blocks), reloads the
    newest intact shard checkpoint re-sharded to the new topology (or
    re-shards the in-memory segment state when running uncheckpointed),
    and re-runs the segment under a fresh budget.  The resumed emits are
    bit-exact vs the fault-free mesh run.  Checkpoints written after a
    reshard carry the new, smaller shard layout.
    """
    program = compiled.program
    n = len(program.segments)
    start, emits = 0, []
    mgr = None
    if directory is not None:
        # keep= (not keep_last=) so keep_last=None means retain-all here
        mgr = CheckpointManager(directory, keep=keep_last,
                                async_save=False)
        if resume:
            for step0 in reversed(mgr.steps()):
                try:
                    tree, extra = restore_checkpoint(
                        directory, step0, _manifest_target(directory, step0))
                except Exception:
                    # torn/corrupt checkpoint: fall back to the previous
                    # retained one instead of failing the whole resume
                    continue
                if extra.get("program") != program.digest():
                    raise ValueError(
                        f"checkpoint at {directory} step {step0} belongs to "
                        f"a different rollout program "
                        f"({extra.get('program')} != {program.digest()})")
                start = int(extra["segment"])
                x = tree["state"]
                emits = [(int(k), v)
                         for k, v in sorted(tree.get("emits", {}).items())]
                break

    attempts = [0] * n
    recovered = [0] * n
    resharded = 0
    t = sum(s.steps for s in program.segments[:start])
    for i in range(start, n):
        seg = {"x": x}

        def _attempt(attempt: int, i=i, seg=seg):
            attempts[i] += 1
            y = compiled.run_segment(i, seg["x"])
            chaos.fire("rollout.segment", segment=int(i),
                       attempt=int(attempt))
            if fault_injector is not None:
                fault_injector(i, attempt)
            return jax.block_until_ready(y)

        while True:
            try:
                x = supervised(_attempt, restart=restart, monitor=monitor,
                               step=i)
                break
            except RuntimeError as e:
                if compiled.mesh is None or not _is_dist_fault(e.__cause__):
                    raise
                # a mesh fault burned the whole retry budget: shrink the
                # mesh (raises when already 1x..x1 — then the failure is
                # real), rebuild the sweeps, reload the newest intact
                # shard checkpoint re-sharded to the survivors, re-run
                # the segment on the fresh budget on_failure just reset
                compiled = _reshard_compiled(compiled, shrink_mesh(compiled.mesh))
                shd = _state_sharding(compiled)
                restored = False
                if mgr is not None:
                    for step0 in reversed(mgr.steps()):
                        try:
                            target = _manifest_target(directory, step0)
                            tree, extra = restore_checkpoint(
                                directory, step0, target,
                                shardings=jax.tree.map(lambda _: shd, target))
                        except Exception:
                            continue
                        if extra.get("program") != program.digest() or \
                                int(extra["segment"]) != i:
                            continue
                        seg["x"] = tree["state"]
                        emits = [(int(k), v) for k, v in
                                 sorted(tree.get("emits", {}).items())]
                        restored = True
                        break
                if not restored:
                    # uncheckpointed (or the segment predates any save):
                    # the in-memory start state re-shards onto the
                    # shrunk mesh directly
                    seg["x"] = jax.device_put(seg["x"], shd)
                resharded += 1
        if attempts[i] > 1:
            recovered[i] = 1
        t += program.segments[i].steps
        if program.segments[i].emit:
            emits.append((t, x))
        if mgr is not None:
            mgr.save(t, _checkpoint_tree(x, emits),
                     extra={"program": program.digest(),
                            "segment": i + 1, "step": t})
    return RolloutResult(final=jnp.asarray(x), emits=tuple(
        (int(s), jnp.asarray(a)) for s, a in emits),
        attempts=tuple(attempts), recovered=tuple(recovered),
        resharded=resharded)
