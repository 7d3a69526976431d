"""Async continuous-batching stencil server over the plan/executable cache.

The ROADMAP's serving story made real: a request stream of independent
user states (arbitrary arrival order, mixed grid shapes) is advanced
``steps`` applications each, at per-state cost amortized four ways:

  1. **plan/compile amortization** — executables come from a
     :class:`repro.core.plan_cache.PlanCache`; a repeated (shape, dtype,
     batch bucket) is a counter-visible cache hit with zero re-planning
     and zero re-tracing.
  2. **batch-in-M execution** — requests with the same spatial shape are
     stacked into power-of-two batch buckets (padded with zero states up
     to the bucket) and advanced by ONE batched executable whose MXU
     contractions fold the bucket into the shared ``dot_general``'s
     slab-side free dimension (``StencilProblem(batch=B)``; kernels
     share the band operands — see ``kernels.stencil_mxu`` for the
     precise operand geometry behind the "batch-in-M" shorthand).
  3. **launch amortization** — one kernel dispatch per chunk serves the
     whole bucket (the planner's ``LAUNCH_OVERHEAD_S / (depth * batch)``
     term).
  4. **dispatch overlap** — the scheduler is ``step()``-driven
     continuous batching: every turn admits whatever is pending RIGHT
     NOW into freshly dispatched buckets (no waiting for a bucket to
     fill) and only then settles the buckets dispatched on earlier
     turns, so host-side stacking/padding of bucket N+1 overlaps device
     execution of bucket N (JAX async dispatch + deferred
     ``block_until_ready``).

Buckets are powers of two so a variable-size stream maps onto a tiny,
highly-reusable set of compiled batch shapes; the padding waste is
bounded by 2x and reported.  **Admission control** keeps the bucket
round-up honest: per shape group the server asks the planner's
bucket-cliff query (:func:`repro.core.planner.max_profitable_batch`,
through the cache's plan memo) for the largest bucket the cost model
still prices as a per-state win, and caps the group BELOW the
batch-scaled VMEM cliff (the 3-D stars at B=8) instead of compiling a
slower executable.

**Rollout serving** (README §Rollout): ``submit_rollout(state,
segments)`` enqueues a whole sweep+update program; the scheduler drives
it one segment per turn through the same buckets — requests whose next
hop shares a (shape, segment-identity) signature batch into ONE cached
one-segment program executable (``PlanCache.get_program``), emitted
intermediates stream incrementally via ``rollout_results(ticket)``, and
the final state settles like any plain result.

Per-request latency (submit -> settled result) is tracked next to the
bucket counters — p50/p95/mean in ``stats()["latency"]`` — and
``submit(state, deadline_s=...)`` counts deadline misses.  Where the
host's turn goes is not counted: the turn carries profiler spans
(``SERVE_SPANS``), written by :class:`jax.profiler.TraceAnnotation` into
the same trace as the device ops and on the same clock, and free while
no profiler runs.  A
**multi-device** server (``devices=jax.devices()``) routes shape groups
round-robin across devices, each with its own :class:`PlanCache`, and
reports a per-device column.

**Fault handling** (DESIGN.md §Robustness) is a graded ladder, driven
by the shared supervision primitives in
:mod:`repro.runtime.fault_tolerance` and exercisable deterministically
through :mod:`repro.runtime.chaos`:

  retry      a failed bucket requeues under a per-shape-group
             :class:`RestartPolicy` clone — exponential backoff, bounded
             budget — instead of a bare requeue; its executable stays
             cold (success accounting sits after readiness).
  fallback   a shape group whose kernel faults persist degrades to the
             ``fallback_backends`` pin (the jnp matrixized reference by
             default) through the normal ``register_backend`` registry;
             results stay BIT-exact and ``stats()["degraded"]`` records
             the mode.
  evict      a device failing ``evict_after`` consecutive buckets leaves
             the round-robin rotation; its sticky shape groups remap to
             surviving devices.  After ``evict_cooldown_s`` it rejoins
             on probation (one strike re-evicts with doubled cooldown)
             and takes one remapped group back as the probe.  A
             MESH-sharded group (``mesh_shape=`` serving) takes the
             partial-mesh rung instead: the eviction SHRINKS the group's
             mesh over the surviving devices (same halving rule as
             ``rollout.executor.shrink_mesh`` — same global grid, fewer
             devices), re-homes it on the shrunk mesh's lead device, and
             counts ``stats()["faults"]["mesh_shrinks"]`` — the serving
             mirror of the rollout executor's reshard-on-failure.
  shed       when the deadline-miss rate over the last ``shed_window``
             deadline-carrying requests crosses ``shed_miss_rate``, the
             lowest-priority class of PENDING requests is shed (their
             tickets fail with :class:`RequestShed`).

**Concurrency**: every public method is thread-safe (one state lock
guards the queues, one step lock serializes scheduler turns; device
waits happen OUTSIDE the state lock so ``submit()``/``results()`` never
block on a sweep).  ``start()`` runs the scheduler on a background
thread so interactive callers never call ``step()`` at all;
``results(ticket, timeout_s=...)`` then blocks until the ticket settles.

    PYTHONPATH=src python -m repro.launch.serve_stencil --cell star2d_r2 \
        --requests 24 --steps 4 --max-batch 8
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import threading
import time
from collections import deque
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.plan_cache import PlanCache
from repro.core.planner import StencilProblem
from repro.core.stencil_spec import PAPER_SUITE, StencilSpec
from repro.launch.compile_cache import enable_compile_cache
from repro.rollout.program import RolloutProgram, Segment, as_segments
from repro.runtime import chaos
from repro.runtime.fault_tolerance import RestartPolicy

__all__ = ["StencilServer", "ServeStats", "RequestShed", "SERVE_SPANS"]

#: The profiler spans of the scheduler's turn, outermost first.  ``turn``
#: wraps one ``step()`` (arg ``turn``, its sequence number); inside it
#: ``stack`` (one compiled stack-and-pad call, ``device_put``),
#: ``lookup`` (the plan cache) and ``launch`` (the executable's dispatch)
#: form one bucket, ``wait`` blocks on a bucket dispatched on an earlier
#: turn and ``book`` splits it (one compiled call) and files its
#: results.  Each bucket's spans share the arg ``bucket_id``; ``launch``
#: lists its ``tickets``.  ``idle`` is the background stepper waiting for
#: work, outside any turn.
SERVE_SPANS = ("stencil.serve.turn", "stencil.serve.stack",
               "stencil.serve.lookup", "stencil.serve.launch",
               "stencil.serve.wait", "stencil.serve.book",
               "stencil.serve.idle")


class RequestShed(RuntimeError):
    """A pending request shed under deadline pressure; claiming its
    ticket raises this (the state was never advanced)."""


def _bucket(n: int, max_batch: int) -> int:
    """Smallest power of two >= n, capped at max_batch."""
    b = 1
    while b < n and b < max_batch:
        b *= 2
    return min(b, max_batch)


@functools.partial(jax.jit, static_argnums=0)
def _stack(bucket: int, *states):
    """The live ``states`` stacked into a ``(bucket, *shape)`` batch whose
    trailing ``bucket - len(states)`` slots are zeros: one compiled call
    per (shape, dtype, bucket, live count)."""
    batch = jnp.stack(states)
    pad = bucket - len(states)
    if pad:
        batch = jnp.concatenate(
            [batch, jnp.zeros((pad,) + batch.shape[1:], batch.dtype)])
    return batch


@functools.partial(jax.jit, static_argnums=1)
def _split(batch, n: int) -> tuple:
    """The first ``n`` states of a settled batch, one array each (the
    padded slots never leave the program): one compiled call per (shape,
    dtype, bucket, live count)."""
    return tuple(batch[i] for i in range(n))


def _shape_str(shape: tuple[int, ...]) -> str:
    return "x".join(str(n) for n in shape)


def _shrunk_shape(shape: tuple[int, ...]) -> tuple[int, ...] | None:
    """One rung down the mesh-shrink ladder: halve the largest axis with
    size > 1 (collapse an odd one to 1) — the same rule as
    :func:`repro.rollout.executor.shrink_mesh`, shape-only so the server
    can pick WHICH surviving devices fill it.  ``None`` when the mesh is
    already a single device."""
    sizes = [(n, j) for j, n in enumerate(shape) if n > 1]
    if not sizes:
        return None
    _, j = max(sizes)
    out = list(shape)
    out[j] = out[j] // 2 if out[j] % 2 == 0 else 1
    return tuple(out)


@dataclasses.dataclass(eq=False)
class _RolloutTask:
    """Scheduler-side progress of one submitted rollout: which segment
    runs next, how many steps completed, and the emitted intermediates
    not yet drained by ``rollout_results``."""
    segments: tuple[Segment, ...]
    seg: int = 0
    done_steps: int = 0
    emits: list = dataclasses.field(default_factory=list)

    @property
    def current(self) -> Segment:
        return self.segments[self.seg]

    @property
    def done(self) -> bool:
        return self.seg >= len(self.segments)

    def signature(self) -> tuple:
        """Bucket-grouping identity of the NEXT segment: requests whose
        next hop is the same (steps, update id, emit) share an
        executable regardless of what the rest of their programs do."""
        s = self.current
        return (s.steps, s.update.update_id if s.update else "", s.emit)


@dataclasses.dataclass(eq=False)
class _Request:
    """One submitted state awaiting its bucket."""
    ticket: int
    state: jnp.ndarray
    submit_t: float
    deadline_s: float | None = None
    rollout: _RolloutTask | None = None
    priority: int = 0
    attempts: int = 0        # dispatch attempts of the CURRENT hop


@dataclasses.dataclass(eq=False)
class _InFlight:
    """One dispatched-but-unsettled bucket (its device work may still be
    running; ``out`` is the unrealized result)."""
    shape: tuple[int, ...]
    requests: list[_Request]
    bucket: int
    entry: object            # CachedExecutable
    out: jnp.ndarray         # (final, emits) pytree for rollout buckets
    t0: float                # dispatch time (perf_counter)
    device: int              # index into the server's device list
    bucket_id: int           # links the bucket's profiler spans
    calls: int               # compiled calls made for it so far
    segment: Segment | None = None   # the rollout hop this bucket ran


@dataclasses.dataclass
class ServeStats:
    """Aggregate serving counters (see :meth:`StencilServer.stats`).

    ``compile_wall_s`` sums each executable's FIRST call (jit trace +
    compile + sweep), dispatch -> settled.  Warm buckets book no time
    here: under overlapped dispatch a bucket's dispatch -> settled span
    includes its queueing behind earlier buckets, so per-bucket wall
    clock double-counts.  Where a warm turn's time goes is read from the
    profiler spans ``SERVE_SPANS`` instead.

    ``dispatches`` counts the compiled calls made for settled buckets:
    a bucket of b > 1 states is one stack, one sweep and one split (3),
    a lone state the sweep alone (1); no other array op runs in the turn.

    ``latencies_s`` records every request's submit -> settled latency
    (the queue + batching + device time a caller actually waits);
    ``deadline_misses`` counts requests whose latency exceeded the
    ``deadline_s`` they were submitted with.

    Fault-ladder counters: ``bucket_failures`` (dispatch or settle
    failures, including injected ones), ``retries`` (failed buckets
    requeued under a retry budget), ``fallbacks`` (shape groups degraded
    to the fallback backend), ``evictions`` (devices removed from the
    rotation), ``mesh_shrinks`` (mesh-sharded groups whose mesh shrank
    over the survivors of an eviction instead of remapping) and ``shed``
    (pending requests dropped under deadline pressure).
    ``rollout_attempts``/``rollout_recovered`` mirror the rollout
    executor's :class:`~repro.rollout.executor.RolloutResult` counters
    at serving granularity: total dispatch attempts of rollout segment
    buckets, and rollout requests whose segment settled only after at
    least one retry.
    """

    requests: int = 0
    batches: int = 0
    padded_states: int = 0
    dispatches: int = 0
    compile_wall_s: float = 0.0  # first-call (trace+compile+sweep) seconds
    deadline_misses: int = 0
    bucket_failures: int = 0
    retries: int = 0
    fallbacks: int = 0
    evictions: int = 0
    mesh_shrinks: int = 0
    rollout_attempts: int = 0
    rollout_recovered: int = 0
    shed: int = 0
    latencies_s: list = dataclasses.field(default_factory=list, repr=False)

    def latency_percentile(self, q: float) -> float:
        """Latency percentile in seconds (0.0 with no settled requests)."""
        if not self.latencies_s:
            return 0.0
        return float(np.percentile(self.latencies_s, q))

    @property
    def p50_latency_s(self) -> float:
        return self.latency_percentile(50)

    @property
    def p95_latency_s(self) -> float:
        return self.latency_percentile(95)


class StencilServer:
    """Continuous-batching request scheduler for one stencil operator.

    One server owns one operator + evolution contract (``spec``,
    ``steps``, ``boundary``, ``dtype``) and serves any stream of states
    of any spatial shape matching ``spec.ndim``:

      * ``submit(state, deadline_s=..., priority=...)`` enqueues a
        state, returns a ticket;
      * ``step()`` runs one scheduler turn — admit every pending request
        into freshly dispatched buckets, then settle the buckets
        dispatched on EARLIER turns (so dispatch of this turn's work
        overlaps the device finishing the last turn's);
      * ``start()`` / ``stop()`` run those turns on a background thread
        instead, making ``submit()`` fire-and-forget;
      * ``results(ticket)`` claims one settled result (``timeout_s=``
        blocks until it settles — the background-stepper accessor);
        ``ready(ticket)`` peeks;
      * ``flush()`` steps until nothing is pending or in flight and
        returns every unclaimed ``{ticket: result}``;
      * ``serve(states)`` is the submit-all-then-flush convenience,
        preserving order (it claims only its own tickets — results
        recovered for OTHER tickets stay claimable).

    ``async_dispatch=False`` degrades to the synchronous PR-5 loop (each
    bucket settles immediately after dispatch) — the reference the async
    path is bit-exact against.  ``admission=False`` disables the
    bucket-cliff cap.  ``devices`` (e.g. ``jax.devices()``) shards the
    server: shape groups route round-robin, one ``PlanCache`` per
    device.  ``mesh_shape=(4,)`` (with ``devices=``) switches to
    MESH-sharded serving instead: each shape group's states are sharded
    over a device mesh of that shape (axis names ``mesh_axes``, spatial
    mapping ``grid_axes`` — defaults ``gx/gy/...`` on the leading grid
    axes) and advanced by the fused distributed stepper; an eviction
    then SHRINKS the group's mesh over the survivors (same halving rule
    as the rollout executor's reshard-on-failure) rather than remapping.

    Fault handling (module docstring; DESIGN.md §Robustness):
    ``restart`` is the per-shape-group retry-budget TEMPLATE (cloned per
    group; ``None`` gives the default 3-strike/50 ms-backoff policy),
    ``fallback_after``/``fallback_backends`` configure the persistent-
    kernel-fault backend degradation (``fallback_after=None`` disables),
    ``evict_after``/``evict_cooldown_s`` the device eviction ladder, and
    ``shed_miss_rate``/``shed_window`` the load shedder (``None``
    disables — the default).

    The plan/executable cache is injectable so several servers (or a
    server plus ad-hoc callers) can share one; by default each server
    owns a fresh :class:`PlanCache` (per device).
    """

    def __init__(self, spec: StencilSpec, steps: int, *,
                 boundary: str = "periodic", dtype: str = "float32",
                 max_batch: int = 8, cache: PlanCache | None = None,
                 backends: Sequence[str] | None = None,
                 hw=None,
                 async_dispatch: bool = True,
                 admission: bool = True, admission_rtol: float = 0.0,
                 devices: Sequence | None = None,
                 mesh_shape: Sequence[int] | None = None,
                 mesh_axes: Sequence[str] | None = None,
                 grid_axes: Sequence[str] | None = None,
                 restart: RestartPolicy | None = None,
                 fallback_after: int | None = 2,
                 fallback_backends: Sequence[str] = ("jnp",),
                 evict_after: int = 3, evict_cooldown_s: float = 2.0,
                 shed_miss_rate: float | None = None,
                 shed_window: int = 16):
        if steps < 0:
            raise ValueError("steps >= 0")
        if max_batch < 1:
            raise ValueError("max_batch >= 1")
        if evict_after < 1:
            raise ValueError("evict_after >= 1")
        if shed_miss_rate is not None and not 0.0 <= shed_miss_rate <= 1.0:
            raise ValueError("shed_miss_rate in [0, 1]")
        self.spec = spec
        self.steps = int(steps)
        self.boundary = boundary
        self.dtype = dtype
        self.max_batch = int(max_batch)
        self.backends = None if backends is None else list(backends)
        self.async_dispatch = bool(async_dispatch)
        self.admission = bool(admission)
        self.admission_rtol = float(admission_rtol)
        self.restart = restart if restart is not None else RestartPolicy(
            max_failures=3, backoff_s=0.05)
        self.fallback_after = fallback_after
        self.fallback_backends = list(fallback_backends)
        self.evict_after = int(evict_after)
        self.evict_cooldown_s = float(evict_cooldown_s)
        self.shed_miss_rate = shed_miss_rate
        self.shed_window = int(shed_window)
        if devices is not None and not list(devices):
            raise ValueError("devices must be non-empty when given")
        self._devices = list(devices) if devices is not None else [None]
        # mesh-sharded serving: each shape group's states are sharded
        # over a Mesh of this shape spanning the server's devices; an
        # eviction SHRINKS a group's mesh instead of remapping it
        if mesh_shape is not None:
            if devices is None:
                raise ValueError("mesh_shape serving needs an explicit "
                                 "devices= list to build meshes from")
            self.mesh_shape = tuple(int(n) for n in mesh_shape)
            if int(np.prod(self.mesh_shape)) > len(self._devices):
                raise ValueError(f"mesh_shape {self.mesh_shape} needs "
                                 f"{int(np.prod(self.mesh_shape))} devices, "
                                 f"got {len(self._devices)}")
            naxes = len(self.mesh_shape)
            self.mesh_axes = (tuple(mesh_axes) if mesh_axes is not None
                              else ("gx", "gy", "gz", "gw")[:naxes])
            if len(self.mesh_axes) != naxes:
                raise ValueError("one mesh axis name per mesh_shape axis")
            self.grid_axes = (tuple(grid_axes) if grid_axes is not None
                              else self.mesh_axes
                              + ("",) * (spec.ndim - naxes))
            if len(self.grid_axes) != spec.ndim:
                raise ValueError(f"grid_axes needs {spec.ndim} entries "
                                 f"('' = unsharded axis)")
        else:
            if mesh_axes is not None or grid_axes is not None:
                raise ValueError("mesh_axes/grid_axes need mesh_shape")
            self.mesh_shape = None
            self.mesh_axes = self.grid_axes = ()
        base = cache if cache is not None else PlanCache(hw=hw)
        #: one PlanCache per device — jit executables are per-device, so
        #: sharing one entry across devices would mix their warm/compile
        #: accounting and recompile under a single ``calls`` counter
        self.caches: list[PlanCache] = [base] + [
            PlanCache(maxsize=base.maxsize, hw=base.hw)
            for _ in self._devices[1:]]
        self.cache = self.caches[0]
        self._pending: list[_Request] = []
        self._inflight: list[_InFlight] = []
        self._rollouts: dict[int, _RolloutTask] = {}
        self._done: dict[int, jnp.ndarray] = {}
        self._failed: dict[int, Exception] = {}
        self._cancelled: set[int] = set()
        self._next_ticket = 0
        self._next_bucket = 0           # bucket_id of the next dispatch
        self._turns = 0                 # scheduler turns begun
        self._caps: dict[tuple[int, ...], int] = {}
        self._group_dev: dict[tuple[int, ...], int] = {}
        self._group_mesh: dict[tuple[int, ...], Mesh] = {}
        self._rr = 0                    # round-robin cursor (active devices)
        # degradation-ladder state -----------------------------------------
        self._retry: dict[tuple[int, ...], RestartPolicy] = {}
        self._group_failures: dict[tuple[int, ...], int] = {}
        self._group_backends: dict[tuple[int, ...], list[str]] = {}
        n_dev = len(self._devices)
        self._dev_fail = [0] * n_dev            # consecutive failures
        self._evicted_until = [None] * n_dev    # monotonic deadline or None
        self._probation = [False] * n_dev
        self._dev_cooldown = [self.evict_cooldown_s] * n_dev
        self._remapped: dict[int, list[tuple[int, ...]]] = {}
        self._deadline_window: deque = deque(maxlen=self.shed_window)
        # concurrency ------------------------------------------------------
        self._lock = threading.RLock()          # queues / results / stats
        self._cv = threading.Condition(self._lock)
        self._step_lock = threading.RLock()     # serializes scheduler turns
        self._work = threading.Event()
        self._stop_event = threading.Event()
        self._stepper: threading.Thread | None = None
        self._stepper_error: Exception | None = None
        self._device_stats = [
            {"device": str(d) if d is not None else "default",
             "batches": 0, "states": 0, "shapes": [],
             "failures": 0, "evictions": 0, "evicted": False}
            for d in self._devices]
        self.stats_ = ServeStats()

    # -- request intake ----------------------------------------------------
    def submit(self, state, *, deadline_s: float | None = None,
               priority: int = 0) -> int:
        """Enqueue one state; returns the ticket results are keyed by.

        ``deadline_s`` is a per-request latency budget in seconds from
        now; a request settling later still returns its result but
        increments ``stats()["deadline_misses"]``.  ``priority`` orders
        load shedding only (HIGHER survives longer; scheduling itself
        stays FIFO-per-shape).  Thread-safe, non-blocking: with the
        background stepper running this is all a caller ever does.
        """
        state = jnp.asarray(state, jnp.dtype(self.dtype))
        if state.ndim != self.spec.ndim:
            raise ValueError(f"state rank {state.ndim} != spec ndim "
                             f"{self.spec.ndim} (submit one state at a "
                             f"time; the server does the batching)")
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._pending.append(_Request(ticket, state, time.perf_counter(),
                                          deadline_s, priority=priority))
            self._stepper_error = None     # new work resumes the stepper
        self._work.set()
        return ticket

    def submit_rollout(self, state, segments, *,
                       deadline_s: float | None = None,
                       priority: int = 0) -> int:
        """Enqueue one state for a ROLLOUT program; returns its ticket.

        ``segments`` is anything :func:`repro.rollout.program.as_segments`
        accepts (``Segment`` objects, bare step counts, ``(steps, update,
        emit)`` tuples).  The scheduler drives the program one segment
        per turn through the SAME bucket machinery as plain requests:
        each ``step()`` advances every in-flight rollout by its next
        segment, batching requests whose next hop shares a (shape,
        segment-identity) signature into one cached program executable —
        so B users at the same point of the same program ride one fused
        sweep.  Emitted intermediates accumulate per ticket and are
        drained incrementally with :meth:`rollout_results`; the FINAL
        state is claimed like any result (:meth:`results` / ``flush()``),
        and latency/deadline accounting spans submit -> final settle.
        """
        state = jnp.asarray(state, jnp.dtype(self.dtype))
        if state.ndim != self.spec.ndim:
            raise ValueError(f"state rank {state.ndim} != spec ndim "
                             f"{self.spec.ndim} (submit one state at a "
                             f"time; the server does the batching)")
        segs = as_segments(segments)
        if not segs:
            raise ValueError("a rollout needs >= 1 segment")
        if self.boundary == "valid":
            raise ValueError("rollout serving needs a shape-preserving "
                             "boundary (valid-mode grids shrink per "
                             "segment, breaking bucket shape grouping)")
        task = _RolloutTask(segments=segs)
        with self._lock:
            ticket = self._next_ticket
            self._next_ticket += 1
            self._rollouts[ticket] = task
            self._pending.append(_Request(ticket, state, time.perf_counter(),
                                          deadline_s, rollout=task,
                                          priority=priority))
            self._stepper_error = None
        self._work.set()
        return ticket

    def rollout_results(self, ticket: int) -> list[tuple[int, jnp.ndarray]]:
        """Drain the emitted intermediates of one rollout so far.

        Returns ``[(cumulative step, state), ...]`` for every emit point
        settled since the last drain (possibly empty — stream more with
        ``step()``).  The ticket stays drainable until the rollout is
        done AND its stream is empty; the final state is claimed
        separately via :meth:`results`.
        """
        with self._lock:
            task = self._rollouts.get(ticket)
            if task is None:
                raise KeyError(f"ticket {ticket} is not a known rollout "
                               f"(plain submit, never submitted, cancelled, "
                               f"or already fully drained)")
            out, task.emits = list(task.emits), []
            if task.done and not task.emits:
                del self._rollouts[ticket]
            return out

    def rollout_done(self, ticket: int) -> bool:
        """Whether a rollout finished its last segment (final result may
        still be unclaimed)."""
        with self._lock:
            task = self._rollouts.get(ticket)
            return task is None or task.done

    def cancel(self, ticket: int):
        """Cancel one request (pending, failed, or mid-rollout).

        Plain tickets: returns ``True`` if anything was dropped.  Rollout
        tickets: the queued program is abandoned and the PARTIAL emits
        settled so far are returned (a ``list``, possibly empty) — the
        ticket's ``_RolloutTask`` no longer leaks in the server.  A
        ticket whose bucket is already IN FLIGHT is settle-then-drop:
        the dispatched device work completes (other tickets share the
        bucket), then the cancelled ticket's result is discarded instead
        of booked.  Already-settled results are NOT cancelled — claim
        them with :meth:`results`.
        """
        with self._lock:
            task = self._rollouts.pop(ticket, None)
            before = len(self._pending)
            self._pending = [r for r in self._pending if r.ticket != ticket]
            removed = len(self._pending) < before
            in_flight = any(r.ticket == ticket
                            for fb in self._inflight for r in fb.requests)
            if in_flight:
                self._cancelled.add(ticket)
            self._failed.pop(ticket, None)
            if removed:
                self._stepper_error = None   # the poison pill may be gone
                self._work.set()
            if task is not None:
                emits, task.emits = list(task.emits), []
                return emits
            return removed or in_flight

    def pending_tickets(self) -> list[int]:
        """Tickets still waiting for a bucket, in submission order."""
        with self._lock:
            return [r.ticket for r in self._pending]

    # -- results -----------------------------------------------------------
    def ready(self, ticket: int) -> bool:
        """Whether ``results(ticket)`` would return without stepping."""
        with self._lock:
            return ticket in self._done

    def _known_unsettled(self, ticket: int) -> bool:
        return (any(r.ticket == ticket for r in self._pending)
                or any(r.ticket == ticket
                       for fb in self._inflight for r in fb.requests)
                or ticket in self._rollouts)

    def results(self, ticket: int, *,
                timeout_s: float | None = None) -> jnp.ndarray:
        """Claim one settled result (removing it from the server).

        Unclaimed results are retained across any number of ``flush()`` /
        ``serve()`` calls — a recovered bucket's tickets are never lost —
        until this accessor (or a ``flush()`` return) hands them out.

        ``timeout_s`` turns this into the BLOCKING accessor for
        background-stepper mode: wait until the ticket settles (or was
        shed/failed — the recorded error re-raises here), raising
        ``TimeoutError`` after ``timeout_s`` seconds.  If the background
        stepper died on an unrecoverable error while the ticket was
        outstanding, that error surfaces here instead of hanging.
        """
        deadline = None if timeout_s is None \
            else time.monotonic() + timeout_s
        with self._cv:
            while True:
                if ticket in self._done:
                    return self._done.pop(ticket)
                err = self._failed.pop(ticket, None)
                if err is not None:
                    raise err
                if not self._known_unsettled(ticket):
                    raise KeyError(
                        f"ticket {ticket} has no claimable result (unknown, "
                        f"cancelled, or already claimed); run step() or "
                        f"flush() to settle pending work") from None
                if timeout_s is None:
                    raise KeyError(
                        f"ticket {ticket} has no claimable result (still "
                        f"pending or in flight); run step() or flush() to "
                        f"settle pending work, or pass timeout_s= to block")
                if self._stepper_error is not None:
                    raise RuntimeError(
                        f"background stepper failed while ticket {ticket} "
                        f"was outstanding: {self._stepper_error}"
                    ) from self._stepper_error
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    raise TimeoutError(
                        f"ticket {ticket} did not settle within "
                        f"{timeout_s}s")
                self._cv.wait(remaining)

    # -- background stepper ------------------------------------------------
    def start(self, poll_s: float = 0.005) -> "StencilServer":
        """Run the scheduler on a daemon thread until :meth:`stop`.

        Each loop iteration is one ordinary :meth:`step` (the step lock
        keeps it safe to ALSO call ``step()``/``flush()`` from other
        threads).  On an unrecoverable turn error (a retry budget
        exhausted) the stepper parks, the error surfaces through blocked
        ``results(timeout_s=...)`` callers, and any ``submit()`` or
        ``cancel()`` resumes stepping.  Idempotent; returns self.
        """
        if poll_s <= 0:
            raise ValueError("poll_s > 0")
        with self._lock:
            if self._stepper is not None and self._stepper.is_alive():
                return self
            self._stop_event = threading.Event()
            self._stepper_error = None
            t = threading.Thread(target=self._stepper_loop, args=(poll_s,),
                                 name="stencil-stepper", daemon=True)
            self._stepper = t
        t.start()
        return self

    def stop(self, timeout_s: float | None = 10.0) -> None:
        """Stop the background stepper (queued work stays queued; a
        later ``flush()``/``start()`` picks it up).  Idempotent."""
        with self._lock:
            t = self._stepper
            self._stepper = None
        if t is None or not t.is_alive():
            return
        self._stop_event.set()
        self._work.set()
        t.join(timeout_s)

    @property
    def running(self) -> bool:
        """Whether the background stepper thread is alive."""
        t = self._stepper
        return t is not None and t.is_alive()

    def _stepper_loop(self, poll_s: float) -> None:
        while not self._stop_event.is_set():
            with self._lock:
                has_work = ((self._pending or self._inflight)
                            and self._stepper_error is None)
            if not has_work:
                with TraceAnnotation("stencil.serve.idle"):
                    self._work.wait(timeout=poll_s)
                self._work.clear()
                continue
            try:
                self.step()
            except Exception as e:       # park; submit()/cancel() resume
                with self._cv:
                    self._stepper_error = e
                    self._cv.notify_all()

    # -- execution ---------------------------------------------------------
    def _problem(self, shape: tuple[int, ...], batch: int,
                 steps: int | None = None,
                 mesh: Mesh | None = None) -> StencilProblem:
        kw = ({"mesh": mesh, "grid_axes": self.grid_axes}
              if mesh is not None else {})
        return StencilProblem(self.spec, shape, dtype=self.dtype,
                              boundary=self.boundary,
                              steps=self.steps if steps is None else steps,
                              batch=batch, **kw)

    def _plan_kwargs(self, shape: tuple[int, ...] | None = None) -> dict:
        """Planner pins for one shape group — the DEGRADED pin once the
        fault ladder demoted the group to the fallback backend."""
        backends = self.backends
        if shape is not None:
            backends = self._group_backends.get(shape, backends)
        return {} if backends is None else {"backends": backends}

    # -- device routing + eviction ----------------------------------------
    def _active_devices(self) -> list[int]:
        return [i for i in range(len(self._devices))
                if self._evicted_until[i] is None]

    def _device_of(self, shape: tuple[int, ...]) -> int:
        """Round-robin shape-group -> device assignment (sticky, so a
        group's buckets always hit the same cache + jit executables;
        evicted devices are skipped).  Under mesh serving the group's
        home is its mesh's LEAD device — failure attribution and cache
        selection follow the mesh, not the round-robin cursor."""
        with self._lock:
            if self.mesh_shape is not None:
                mesh = self._group_mesh_for(shape)
                di = self._dev_index(mesh.devices.flat[0])
                if self._group_dev.get(shape) != di:
                    self._group_dev[shape] = di
                    name = _shape_str(shape)
                    if name not in self._device_stats[di]["shapes"]:
                        self._device_stats[di]["shapes"].append(name)
                return di
            di = self._group_dev.get(shape)
            if di is None or self._evicted_until[di] is not None:
                active = self._active_devices() or [0]
                di = active[self._rr % len(active)]
                self._rr += 1
                self._group_dev[shape] = di
                name = _shape_str(shape)
                if name not in self._device_stats[di]["shapes"]:
                    self._device_stats[di]["shapes"].append(name)
            return di

    def _dev_index(self, dev) -> int:
        for i, d in enumerate(self._devices):
            if d is dev:
                return i
        return 0

    def _group_mesh_for(self, shape: tuple[int, ...]) -> Mesh:
        """The shape group's serving mesh, built lazily over the ACTIVE
        devices at the configured ``mesh_shape`` (shrunk down the same
        halving ladder if evictions already thinned the rotation below
        it).  Once built the mesh is sticky — it changes only through
        :meth:`_evict_device`'s shrink rung (lock held)."""
        mesh = self._group_mesh.get(shape)
        if mesh is None:
            active = [self._devices[i] for i in self._active_devices()]
            mshape: tuple[int, ...] | None = self.mesh_shape
            while int(np.prod(mshape)) > len(active):
                mshape = _shrunk_shape(mshape)
                if mshape is None:   # unreachable: the last device stays
                    raise RuntimeError("no active devices left for a mesh")
            n = int(np.prod(mshape))
            mesh = Mesh(np.array(active[:n], dtype=object).reshape(mshape),
                        self.mesh_axes)
            self._group_mesh[shape] = mesh
        return mesh

    def _shrink_group_mesh(self, mesh: Mesh) -> Mesh | None:
        """The largest halving of ``mesh`` that fits on its surviving
        (non-evicted) devices, preserving their order — ``None`` when a
        single-device mesh cannot shrink further (lock held)."""
        gone = {id(self._devices[i])
                for i, u in enumerate(self._evicted_until) if u is not None}
        survivors = [d for d in mesh.devices.flat if id(d) not in gone]
        shape: tuple[int, ...] | None = tuple(mesh.devices.shape)
        while True:
            shape = _shrunk_shape(shape)
            if shape is None:
                return None
            n = int(np.prod(shape))
            if n <= len(survivors):
                return Mesh(np.array(survivors[:n],
                                     dtype=object).reshape(shape),
                            self.mesh_axes)

    def _evict_device(self, di: int, now: float) -> None:
        """Remove one device from the rotation and remap its sticky
        groups to survivors (lock held).  A MESH-sharded group whose
        mesh contains the evicted device takes the partial-mesh rung
        instead: its mesh SHRINKS over the surviving devices (same grid,
        fewer devices — the serving mirror of the rollout executor's
        reshard-on-failure) and the group re-homes on the shrunk mesh's
        lead device; only a mesh that cannot shrink falls back to the
        plain rebuild-over-survivors remap."""
        if len(self._active_devices()) <= 1:
            return                        # never evict the last device
        self._evicted_until[di] = now + self._dev_cooldown[di]
        if self._probation[di]:
            self._dev_cooldown[di] *= 2.0  # probation strike: back off more
        self._probation[di] = False
        self._dev_fail[di] = 0
        self._device_stats[di]["evictions"] += 1
        self._device_stats[di]["evicted"] = True
        self.stats_.evictions += 1
        dead = self._devices[di]
        shrunk: set[tuple[int, ...]] = set()
        for shape, mesh in list(self._group_mesh.items()):
            if dead is None or not any(d is dead for d in mesh.devices.flat):
                continue
            new_mesh = self._shrink_group_mesh(mesh)
            if new_mesh is None:
                # a 1-device mesh lost its device: rebuild lazily over
                # whatever survives, via the normal remap path
                del self._group_mesh[shape]
                continue
            self._group_mesh[shape] = new_mesh
            self._group_dev[shape] = self._dev_index(new_mesh.devices.flat[0])
            self._caps.pop(shape, None)   # new mesh -> new cache key/cap
            self.stats_.mesh_shrinks += 1
            shrunk.add(shape)
        moved = [s for s, d in self._group_dev.items()
                 if d == di and s not in shrunk]
        for shape in moved:
            del self._group_dev[shape]    # next _device_of reassigns
            self._remapped.setdefault(di, []).append(shape)

    def _readmit_devices(self) -> None:
        """Cooldown probe: an evicted device whose cooldown expired
        rejoins the rotation on probation, taking back ONE of its
        remapped groups so the probe actually runs traffic."""
        now = time.monotonic()
        with self._lock:
            for di, until in enumerate(self._evicted_until):
                if until is None or now < until:
                    continue
                self._evicted_until[di] = None
                self._probation[di] = True
                self._dev_fail[di] = 0
                self._device_stats[di]["evicted"] = False
                for shape in self._remapped.pop(di, []):
                    self._group_dev[shape] = di   # the probe group
                    break

    def bucket_cap(self, shape: tuple[int, ...]) -> int:
        """Admission-control bucket cap for one shape group, memoized.

        With ``admission`` on, the planner's bucket-cliff query walks the
        modelled per-state cost over the serving buckets (through the
        device's plan memo, so the walk's plans are reused by the later
        compiling miss) and the group is capped at the largest bucket
        still priced as a win — below the batch-scaled VMEM cliff.
        """
        cap = self._caps.get(shape)
        if cap is None:
            # mesh serving skips the cliff walk: the admission model
            # prices single-device plans, not per-shard distributed ones
            if self.mesh_shape is not None:
                cap = self.max_batch
            elif self.admission and self.max_batch > 1:
                di = self._device_of(shape)
                cap = self.caches[di].bucket_cap(
                    self._problem(shape, 1), self.max_batch,
                    rtol=self.admission_rtol, **self._plan_kwargs(shape))
            else:
                cap = self.max_batch
            self._caps[shape] = cap
        return cap

    def _dispatch_bucket(self, shape: tuple[int, ...], cap: int,
                         chunk: list[_Request]) -> _InFlight:
        """Stack/pad one <= cap group and launch it (async).

        A bucket of b > 1 is stacked and zero-padded by one compiled call
        (:func:`_stack`); a lone state goes to the unbatched executable
        as it is.

        Plain requests run the server's ``steps``-sweep executable; a
        rollout group (all members share the next-segment signature, by
        ``_admit``'s grouping) runs a ONE-segment program executable from
        ``PlanCache.get_program`` — keyed by the segment identity, so it
        can never alias the plain sweep, and shared by every rollout
        whose next hop matches.
        """
        b = _bucket(len(chunk), cap)
        di = self._device_of(shape)
        dev = self._devices[di]
        with self._lock:
            bid = self._next_bucket
            self._next_bucket += 1
            mesh = (self._group_mesh_for(shape)
                    if self.mesh_shape is not None else None)
            seg = chunk[0].rollout.current if chunk[0].rollout else None
            for r in chunk:
                r.attempts += 1
            if seg is not None:
                self.stats_.rollout_attempts += len(chunk)
        with TraceAnnotation("stencil.serve.stack", bucket_id=bid, size=b,
                             shape=_shape_str(shape)):
            if b == 1:
                arg, calls = chunk[0].state, 0
            else:
                arg, calls = _stack(b, *(r.state for r in chunk)), 1
            if mesh is not None:
                lead = [None] if b > 1 else []
                axes = [a if a else None for a in self.grid_axes]
                arg = jax.device_put(arg, NamedSharding(
                    mesh, PartitionSpec(*(lead + axes))))
            elif dev is not None:
                arg = jax.device_put(arg, dev)
        with TraceAnnotation("stencil.serve.lookup", bucket_id=bid):
            if seg is not None:
                program = RolloutProgram(
                    self._problem(shape, b, steps=seg.steps, mesh=mesh),
                    (seg,))
                entry = self.caches[di].get_program(
                    program, mesh=mesh, **self._plan_kwargs(shape))
            else:
                entry = self.caches[di].get(
                    self._problem(shape, b, mesh=mesh), mesh=mesh,
                    **self._plan_kwargs(shape))
        chaos.fire("serve.dispatch", shape=_shape_str(shape), device=di,
                   bucket=b)
        t0 = time.perf_counter()
        # dispatch only — readiness (and the entry's success accounting)
        # is deferred to _settle, so a failed first call stays cold and
        # host-side prep of the next bucket overlaps this device work
        with TraceAnnotation("stencil.serve.launch", bucket_id=bid,
                             requests=len(chunk),
                             tickets=" ".join(str(r.ticket) for r in chunk)):
            out = entry.dispatch(arg)
        return _InFlight(shape=shape, requests=list(chunk), bucket=b,
                         entry=entry, out=out, t0=t0, device=di,
                         bucket_id=bid, calls=calls + 1, segment=seg)

    def _salvage(self) -> None:
        """Settle whatever is in flight before propagating a primary
        error; a secondary settle failure already requeued its requests,
        so it is deliberately swallowed here."""
        try:
            self._settle(list(self._inflight))
        except Exception:
            pass

    # -- the fault ladder --------------------------------------------------
    def _bucket_failure(self, shape: tuple[int, ...], device: int,
                        err: Exception,
                        tickets: list[int]) -> Exception | None:
        """One failed bucket through the degradation ladder.

        Books the failure, advances the backend-fallback and
        device-eviction counters, then charges the shape group's retry
        budget: returns ``None`` when a retry is scheduled (after
        sleeping the backoff) or the terminal error once the budget is
        exhausted (the caller raises; the requests are back in the
        queue either way).
        """
        now = time.monotonic()
        with self._lock:
            self.stats_.bucket_failures += 1
            self._device_stats[device]["failures"] += 1
            self._dev_fail[device] += 1
            self._group_failures[shape] = self._group_failures.get(
                shape, 0) + 1
            # ladder rung 2: persistent kernel faults -> degrade the
            # group to the fallback backend pin (bit-exact by the cross-
            # backend parity guarantees; a NEW cache key, so the faulty
            # executable is simply never asked again)
            if (self.fallback_after is not None
                    and self._group_failures[shape] >= self.fallback_after
                    and self._group_backends.get(shape)
                    != self.fallback_backends
                    and self.backends != self.fallback_backends):
                self._group_backends[shape] = list(self.fallback_backends)
                self._caps.pop(shape, None)   # re-walk the cap if needed
                self.stats_.fallbacks += 1
            # ladder rung 3: a persistently failing DEVICE leaves the
            # rotation (probation devices get one strike)
            strikes = 1 if self._probation[device] else self.evict_after
            if self._dev_fail[device] >= strikes:
                self._evict_device(device, now)
            pol = self._retry.get(shape)
            if pol is None:
                pol = self._retry[shape] = self.restart.clone()
        try:
            delay = pol.on_failure(err)
        except RuntimeError:
            return ValueError(
                f"serving bucket of shape {shape} failed for tickets "
                f"{tickets}: {err} (retry budget exhausted after "
                f"{pol.max_failures} retries); the failed requests stay "
                f"queued and completed results are returned by the next "
                f"flush()")
        with self._lock:
            self.stats_.retries += 1
        time.sleep(delay)
        return None

    def _maybe_shed(self) -> None:
        """Ladder rung 4: deadline pressure sheds the lowest-priority
        PENDING class (requests already dispatched always settle)."""
        if self.shed_miss_rate is None:
            return
        with self._cv:
            win = self._deadline_window
            if len(win) < self.shed_window:
                return
            if sum(win) / len(win) <= self.shed_miss_rate:
                return
            prios = {r.priority for r in self._pending}
            if len(prios) < 2:
                return     # nothing is "lowest" in a uniform queue
            low = min(prios)
            shed = [r for r in self._pending if r.priority == low]
            self._pending = [r for r in self._pending if r.priority != low]
            for r in shed:
                self._rollouts.pop(r.ticket, None)
                self._failed[r.ticket] = RequestShed(
                    f"ticket {r.ticket} (priority {r.priority}) shed: "
                    f"deadline-miss rate over the last {len(win)} "
                    f"deadline-carrying requests exceeded "
                    f"{self.shed_miss_rate}")
            self.stats_.shed += len(shed)
            win.clear()     # fresh window before the next shed decision
            self._cv.notify_all()

    def _admit(self) -> None:
        """Admit every pending request into dispatched buckets NOW.

        Continuous batching: buckets form from whatever has been
        submitted by this turn (grouped by shape, capped by admission
        control) — a late submit rides the next turn's buckets instead
        of waiting for this group to fill.  A request leaves the queue
        the moment its bucket dispatches; a bucket that fails to PLAN
        (bucket-cap/planner errors are deterministic) fails fast, while
        a dispatch failure of a planned bucket goes through the retry
        ladder like a settle failure.  Either way failed requests stay
        queued and the raised error names the shape and tickets.
        """
        self._readmit_devices()
        self._maybe_shed()
        with self._lock:
            if not self._pending:
                return
            # group by (shape, next-hop signature): plain requests carry
            # the empty signature, a rollout the identity of its NEXT
            # segment — so plain sweeps never share a bucket with rollout
            # hops, and rollouts batch exactly when their next
            # executables coincide
            by_shape: dict[tuple, list[_Request]] = {}
            for r in self._pending:
                sig = r.rollout.signature() if r.rollout else ()
                by_shape.setdefault((tuple(r.state.shape), sig),
                                    []).append(r)
        for shape, _sig in sorted(by_shape):
            group = by_shape[(shape, _sig)]
            try:
                cap = self.bucket_cap(shape)
            except Exception as e:
                self._salvage()
                raise ValueError(
                    f"serving bucket of shape {shape} failed for tickets "
                    f"{[r.ticket for r in group]}: {e}; the failed requests "
                    f"stay queued and completed results are returned by the "
                    f"next flush()") from e
            for i in range(0, len(group), cap):
                with self._lock:
                    # revalidate against concurrent cancel()
                    chunk = [r for r in group[i:i + cap]
                             if r in self._pending]
                if not chunk:
                    continue
                try:
                    fb = self._dispatch_bucket(shape, cap, chunk)
                except Exception as e:
                    di = self._device_of(shape)
                    terminal = self._bucket_failure(
                        shape, di, e, [r.ticket for r in chunk])
                    if terminal is None:
                        continue          # requests stay queued; next turn
                    self._salvage()
                    raise terminal from e
                with self._lock:
                    ids = {r.ticket for r in chunk}
                    still = {r.ticket for r in self._pending
                             if r.ticket in ids}
                    # a ticket cancelled DURING the dispatch window is
                    # settle-then-drop like any in-flight cancel
                    self._cancelled.update(ids - still)
                    self._pending = [r for r in self._pending
                                     if r.ticket not in ids]
                    self._inflight.append(fb)
                if not self.async_dispatch:
                    self._settle([fb])

    def _settle(self, buckets: list[_InFlight]) -> int:
        """Block on the given in-flight buckets, book stats + latencies,
        move results to ``_done``.  A bucket whose deferred device work
        failed goes through the fault ladder (:meth:`_bucket_failure`):
        its requests requeue under the shape group's retry budget, its
        executable stays COLD (the success accounting sits after
        readiness), and only an exhausted budget raises — after the rest
        of the buckets settled."""
        settled = 0
        failure: Exception | None = None
        for fb in buckets:
            # the bucket stays in _inflight THROUGH the device wait so a
            # concurrent results()/cancel() always sees its tickets; it
            # leaves only under the lock, at booking or requeue
            with self._lock:
                if fb not in self._inflight:
                    continue  # already settled by an earlier salvage pass
            try:
                chaos.fire("serve.settle", shape=_shape_str(fb.shape),
                           device=fb.device)
                with TraceAnnotation("stencil.serve.wait",
                                     bucket_id=fb.bucket_id):
                    jax.block_until_ready(fb.out)
            except Exception as e:
                with self._lock:
                    self._inflight.remove(fb)
                    keep = [r for r in fb.requests
                            if r.ticket not in self._cancelled]
                    for r in fb.requests:
                        if r.ticket in self._cancelled:
                            self._cancelled.discard(r.ticket)
                            self._rollouts.pop(r.ticket, None)
                    self._pending.extend(keep)
                terminal = self._bucket_failure(
                    fb.shape, fb.device, e, [r.ticket for r in keep])
                if terminal is not None and failure is None:
                    failure = terminal
                    failure.__cause__ = e
                continue
            now = time.perf_counter()
            dt = now - fb.t0
            with TraceAnnotation("stencil.serve.book",
                                 bucket_id=fb.bucket_id,
                                 requests=len(fb.requests)):
                # a rollout bucket's out is the program pytree
                # (final, emits); the one-segment program's emit (if
                # any) IS the final state.  The split runs before
                # the lock: booking below only assigns its parts
                final = fb.out[0] if fb.segment is not None else fb.out
                if fb.bucket == 1:
                    parts = (final,)
                else:
                    parts = _split(final, len(fb.requests))
                    fb.calls += 1
                with self._cv:
                    self._inflight.remove(fb)
                    st = self.stats_
                    if not fb.entry.mark_ready(dt):
                        st.compile_wall_s += dt
                    st.batches += 1
                    st.padded_states += fb.bucket - len(fb.requests)
                    st.dispatches += fb.calls
                    ds = self._device_stats[fb.device]
                    ds["batches"] += 1
                    ds["states"] += len(fb.requests)
                    # success resets the ladder counters of group/device
                    self._dev_fail[fb.device] = 0
                    self._probation[fb.device] = False
                    self._dev_cooldown[fb.device] = self.evict_cooldown_s
                    self._group_failures[fb.shape] = 0
                    pol = self._retry.get(fb.shape)
                    if pol is not None:
                        pol.on_success()
                    for r, res in zip(fb.requests, parts):
                        if r.ticket in self._cancelled:
                            # settle-then-drop: the bucket ran, the
                            # cancelled ticket's share is discarded
                            self._cancelled.discard(r.ticket)
                            self._rollouts.pop(r.ticket, None)
                            continue
                        if r.rollout is not None:
                            task = r.rollout
                            if r.attempts > 1:
                                # this segment settled only after a
                                # retry — the serving mirror of
                                # RolloutResult.recovered
                                st.rollout_recovered += 1
                            task.seg += 1
                            task.done_steps += fb.segment.steps
                            if fb.segment.emit:
                                # one-segment program: one emit, == res
                                task.emits.append((task.done_steps, res))
                            if not task.done:
                                # requeue for the next segment,
                                # preserving the submit clock (latency
                                # spans the whole program) but with a
                                # fresh attempt count for the next hop
                                self._pending.append(dataclasses.replace(
                                    r, state=res, attempts=0))
                                continue
                        self._done[r.ticket] = res
                        st.requests += 1
                        lat = now - r.submit_t
                        st.latencies_s.append(lat)
                        if r.deadline_s is not None:
                            miss = lat > r.deadline_s
                            st.deadline_misses += miss
                            self._deadline_window.append(int(miss))
                        settled += 1
                    self._cv.notify_all()
        if failure is not None:
            raise failure
        return settled

    def step(self) -> int:
        """One scheduler turn; returns how many requests settled.

        Admits every pending request into freshly dispatched buckets,
        then settles the buckets dispatched on EARLIER turns — the
        double-buffering discipline: while the device works on last
        turn's buckets, this turn's stacking/padding/dispatch happens on
        the host, and only then does the host block.  Turns serialize on
        the step lock (safe alongside the background stepper); device
        waits happen outside the state lock, so concurrent ``submit()``
        never waits on a sweep.
        """
        with self._step_lock:
            self._turns += 1
            with TraceAnnotation("stencil.serve.turn", turn=self._turns):
                with self._lock:
                    before = self.stats_.requests
                    prior = list(self._inflight)
                self._admit()
                if self.async_dispatch:
                    self._settle(prior)
                with self._lock:
                    return self.stats_.requests - before

    def flush(self) -> dict[int, jnp.ndarray]:
        """Step until nothing is pending or in flight; return every
        unclaimed ``{ticket: evolved state}`` (the claim).

        Lossless bucket-by-bucket progress: a request leaves the queue
        the moment its bucket DISPATCHES, and its result is retained
        once settled.  If a bucket fails, its requests retry under the
        shape group's budget; once the budget exhausts the error names
        the offending shape/tickets, the failed bucket's requests stay
        queued (cancel or resubmit them), already-completed buckets are
        neither recomputed nor double-counted, and their results are
        returned by the next successful ``flush()`` — or individually by
        :meth:`results`, which is how ``serve()`` claims, so one
        caller's flush can never strand another's tickets.
        """
        while True:
            with self._lock:
                if not (self._pending or self._inflight):
                    break
            self.step()
        with self._lock:
            results, self._done = self._done, {}
            return results

    def serve(self, states: Sequence) -> list[jnp.ndarray]:
        """Submit every state, flush, return results in submission order.

        Claims ONLY its own tickets: results the flush recovered for
        tickets submitted elsewhere go back to the server, still
        claimable via :meth:`results` or the next ``flush()``.
        """
        tickets = [self.submit(s) for s in states]
        results = self.flush()
        out = [results.pop(t) for t in tickets]
        with self._lock:
            self._done.update(results)
        return out

    __call__ = serve

    # -- reporting ---------------------------------------------------------
    def reset_stats(self) -> None:
        """Zero the serving counters (cache counters are left alone) —
        e.g. between a warm-up pass and a measured pass."""
        with self._lock:
            self.stats_ = ServeStats()

    def stats(self) -> dict:
        """Serving counters + latency percentiles + admission caps +
        fault-ladder state + per-device columns, merged with the
        plan-cache stats (summed across devices; each device row carries
        its own)."""
        with self._lock:
            st = self.stats_
            s = dataclasses.asdict(st)
            lat = s.pop("latencies_s")
            s["latency"] = {
                "count": len(lat),
                "p50_s": st.p50_latency_s,
                "p95_s": st.p95_latency_s,
                "mean_s": float(np.mean(lat)) if lat else 0.0,
                "max_s": float(np.max(lat)) if lat else 0.0,
            }
            s["admission"] = {_shape_str(shape): cap
                              for shape, cap in sorted(self._caps.items())}
            s["faults"] = {
                "bucket_failures": st.bucket_failures,
                "retries": st.retries,
                "fallbacks": st.fallbacks,
                "evictions": st.evictions,
                "mesh_shrinks": st.mesh_shrinks,
                "rollout_attempts": st.rollout_attempts,
                "rollout_recovered": st.rollout_recovered,
                "shed": st.shed,
            }
            s["degraded"] = {_shape_str(shape): list(b) for shape, b
                             in sorted(self._group_backends.items())}
            if self.mesh_shape is not None:
                s["meshes"] = {
                    _shape_str(shape): _shape_str(m.devices.shape)
                    for shape, m in sorted(self._group_mesh.items())}
            s["stepper"] = {"running": self.running,
                            "error": str(self._stepper_error)
                            if self._stepper_error else None}
            per_dev = []
            for ds, cache in zip(self._device_stats, self.caches):
                row = dict(ds)
                row["plan_cache"] = cache.stats()
                per_dev.append(row)
            s["devices"] = per_dev
            if len(self.caches) == 1:
                s["plan_cache"] = self.cache.stats()
            else:
                merged: dict[str, int] = {}
                for cache in self.caches:
                    for k, v in cache.stats().items():
                        merged[k] = merged.get(k, 0) + v
                s["plan_cache"] = merged
            return s


# ---------------------------------------------------------------------------
# CLI: synthesize a mixed request stream and report throughput + latency
# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="star2d_r2",
                    help="PAPER_SUITE cell to serve")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--grid", type=int, default=48,
                    help="base spatial extent (a second shape at 2/3 of it "
                         "is mixed in to exercise shape grouping)")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--boundary", default="periodic")
    ap.add_argument("--backends", default="",
                    help="comma-separated backend pin (default: the "
                         "planner's full search)")
    ap.add_argument("--sync", action="store_true",
                    help="synchronous dispatch (settle each bucket "
                         "immediately) instead of overlapped")
    ap.add_argument("--no-admission", action="store_true",
                    help="disable the bucket-cliff admission cap")
    ap.add_argument("--all-devices", action="store_true",
                    help="route shape groups round-robin over jax.devices()")
    ap.add_argument("--background", action="store_true",
                    help="drive the scheduler from the background stepper "
                         "thread (submit + blocking results) instead of "
                         "serve()")
    ap.add_argument("--chaos-settle", type=float, default=0.0,
                    help="inject seeded settle faults at this rate (the "
                         "retry ladder must recover; see "
                         "repro.runtime.chaos)")
    args = ap.parse_args()
    enable_compile_cache()

    spec = PAPER_SUITE()[args.cell]
    backends = [b for b in args.backends.split(",") if b] or None
    server = StencilServer(spec, args.steps, boundary=args.boundary,
                           max_batch=args.max_batch, backends=backends,
                           async_dispatch=not args.sync,
                           admission=not args.no_admission,
                           devices=jax.devices() if args.all_devices
                           else None)
    rng = np.random.default_rng(0)
    shapes = [(args.grid,) * spec.ndim,
              (max(2 * args.grid // 3, 8),) * spec.ndim]
    states = [rng.normal(size=shapes[i % len(shapes)]).astype(np.float32)
              for i in range(args.requests)]

    def run_pass():
        if args.background:
            server.start()
            try:
                tickets = [server.submit(s) for s in states]
                return [server.results(t, timeout_s=300.0) for t in tickets]
            finally:
                server.stop()
        return server.serve(states)

    plan = chaos.FaultPlan(seed=0)
    if args.chaos_settle > 0:
        plan.rule("serve.settle", rate=args.chaos_settle)
    with plan:
        t0 = time.perf_counter()
        run_pass()
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        run_pass()
        warm = time.perf_counter() - t0

    s = server.stats()
    mode = "sync" if args.sync else "async"
    if args.background:
        mode += "+background"
    print(f"served {s['requests']} states of {args.cell} x {args.steps} "
          f"steps in {s['batches']} batches ({mode} dispatch, "
          f"{s['padded_states']} padded slots)")
    print(f"cold pass {cold * 1e3:.1f} ms (plans + compiles: "
          f"{s['compile_wall_s'] * 1e3:.1f} ms first calls), warm pass "
          f"{warm * 1e3:.1f} ms -> "
          f"{args.requests / warm:.1f} states/s warm")
    print(f"latency p50 {s['latency']['p50_s'] * 1e3:.1f} ms / "
          f"p95 {s['latency']['p95_s'] * 1e3:.1f} ms; "
          f"plan cache: {s['plan_cache']['hits']} hits / "
          f"{s['plan_cache']['misses']} misses "
          f"(size {s['plan_cache']['size']})")
    caps = ", ".join(f"{k}<={v}" for k, v in s["admission"].items())
    print(f"admission caps: {caps or '-'}")
    if args.chaos_settle > 0:
        f = s["faults"]
        print(f"chaos: {plan.fired()} injected faults -> "
              f"{f['bucket_failures']} bucket failures, {f['retries']} "
              f"retries, {f['fallbacks']} fallbacks (all recovered)")
    if len(s["devices"]) > 1:
        print("device        batches  states  fails  shapes")
        for row in s["devices"]:
            print(f"{row['device']:<13s} {row['batches']:7d} "
                  f"{row['states']:7d} {row['failures']:6d}  "
                  f"{','.join(row['shapes']) or '-'}")


if __name__ == "__main__":
    main()
