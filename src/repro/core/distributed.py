"""Distributed stencil execution: domain decomposition + halo exchange.

The paper's in-core scheduling (§4.3: fix the output block, stream inputs)
scales out unchanged: each device owns a block of the grid, halos are the
inter-device analogue of the overlapping BlockSpec windows, and the exchange
is two ``lax.ppermute`` pairs per axis under ``shard_map``.

Compute/communication overlap: the update is split into an *interior* region
(needs no halo) and boundary strips (need it).  The permutes are issued
first; XLA's async collectives then overlap the interior matmuls with the
wire time — the schedule is visible in the compiled HLO
(collective-permute-start ... interior dots ... collective-permute-done).

Fused distributed sweeps (DESIGN.md §Planner): a chunk of ``T`` steps
exchanges ONE ``T*r``-deep halo and then applies the T-fold self-correlated
operator (``temporal.fuse_steps``) to the deep-haloed block — communication
drops T-fold alongside the HBM traffic.  For Dirichlet-0 boundaries the
fused operator is exact only at distance >= ``T*r`` from the *global*
boundary, so edge strips are recomputed by ``T`` unfused steps over the
already-exchanged deep halo with per-step clamping applied through a
global-position mask (SPMD-uniform: every device runs the same program and
the mask is the identity away from the global edge).

The same machinery drives the production-mesh PDE example and the
multi-pod dry-run for the paper's own workloads.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import temporal
from repro.core.engine import StencilEngine
from repro.core.stencil_spec import StencilSpec
from repro.runtime import chaos
from repro.runtime.chaos import FaultError

__all__ = ["halo_exchange", "distributed_stencil_step",
           "distributed_fused_chunk", "make_distributed_stepper",
           "make_fused_distributed_stepper", "DistributedStepper",
           "INTERIOR_STEP", "INTERIOR_SWEEP"]

#: kernel names of the overlap's interior recompute (a one-step or
#: operator-fused core, an in-kernel sweep core), so that a profiler trace
#: tells it from the chunk's own ``stencil_step`` / ``stencil_sweep``
INTERIOR_STEP = "stencil_step_interior"
INTERIOR_SWEEP = "stencil_sweep_interior"


def _exchange_axis(block: jnp.ndarray, axis: int, r: int, mesh_axis: str,
                   periodic: bool) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Send our boundary strips to neighbours along one mesh axis.

    Returns (lo_halo, hi_halo): the neighbour strips that belong on our low /
    high side.  With non-periodic boundaries the edge devices receive zeros
    (Dirichlet-0), matching the single-device engine's boundary="zero".
    """
    n_dev = lax.axis_size(mesh_axis)
    idx = lax.axis_index(mesh_axis)

    lo_strip = lax.slice_in_dim(block, 0, r, axis=axis)            # our low rows
    hi_strip = lax.slice_in_dim(block, block.shape[axis] - r, block.shape[axis], axis=axis)

    fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]             # i -> i+1
    bwd = [(i, (i - 1) % n_dev) for i in range(n_dev)]             # i -> i-1
    # halo on our low side comes from the previous device's high strip
    lo_halo = lax.ppermute(hi_strip, mesh_axis, fwd)
    hi_halo = lax.ppermute(lo_strip, mesh_axis, bwd)
    if not periodic:
        zero = jnp.zeros_like(lo_halo)
        lo_halo = jnp.where(idx == 0, zero, lo_halo)
        hi_halo = jnp.where(idx == n_dev - 1, jnp.zeros_like(hi_halo), hi_halo)
    return lo_halo, hi_halo


def halo_exchange(block: jnp.ndarray, r: int, mesh_axes: dict[int, str],
                  periodic: bool = True) -> jnp.ndarray:
    """Pad ``block`` with width-r halos fetched from mesh neighbours.

    mesh_axes: {array_axis: mesh_axis_name} for each decomposed axis.
    Must run inside shard_map.
    """
    out = block
    for axis, mesh_axis in sorted(mesh_axes.items()):
        lo, hi = _exchange_axis(out, axis, r, mesh_axis, periodic)
        out = jnp.concatenate([lo, out, hi], axis=axis)
    return out


def _pad_local_axes(block: jnp.ndarray, width: int, spec_ndim: int,
                    mesh_axes: dict[int, str], periodic: bool) -> jnp.ndarray:
    """Boundary-pad the spatial axes that are NOT decomposed over the mesh.

    An unsharded spatial axis lives entirely on every device, so its
    boundary condition is applied locally: wrap for periodic, zeros for
    Dirichlet-0 — the same semantics the exchange gives sharded axes.
    """
    lead = block.ndim - spec_ndim
    pad = [(0, 0)] * block.ndim
    local = False
    for axis in range(lead, block.ndim):
        if axis not in mesh_axes:
            pad[axis] = (width, width)
            local = True
    if not local or width == 0:
        return block
    return jnp.pad(block, pad, mode="wrap" if periodic else "constant")


def _haloed_input(block: jnp.ndarray, width: int, spec_ndim: int,
                  mesh_axes: dict[int, str], periodic: bool) -> jnp.ndarray:
    """Block extended by ``width`` on every spatial axis: local pads on
    unsharded axes, neighbour exchange on sharded axes."""
    out = _pad_local_axes(block, width, spec_ndim, mesh_axes, periodic)
    return halo_exchange(out, width, mesh_axes, periodic)


def _mask_outside_domain(s: jnp.ndarray, start_off: dict[int, int],
                         axinfo: dict[int, tuple]) -> jnp.ndarray:
    """Zero every position of ``s`` that lies outside the GLOBAL domain.

    ``start_off[axis]`` is the global offset of s's local index 0 relative
    to this device's owned-block start; ``axinfo[axis] = (shard_index,
    n_owned, n_global)``.  Multiplying by the mask before each unfused step
    is exactly per-step Dirichlet-0 clamping, expressed SPMD-uniformly (the
    mask is all-ones on devices away from the global edge).
    """
    out = s
    for axis, (idx, n_owned, n_global) in axinfo.items():
        g0 = idx * n_owned + start_off[axis]
        pos = g0 + jnp.arange(s.shape[axis])
        mask = (pos >= 0) & (pos < n_global)
        shape = [1] * s.ndim
        shape[axis] = s.shape[axis]
        out = out * mask.reshape(shape).astype(s.dtype)
    return out


def _axis_info(block: jnp.ndarray, spec_ndim: int,
               mesh_axes: dict[int, str]) -> dict[int, tuple]:
    lead = block.ndim - spec_ndim
    info = {}
    for axis in range(lead, block.ndim):
        n_owned = block.shape[axis]
        if axis in mesh_axes:
            n_dev = lax.axis_size(mesh_axes[axis])
            idx = lax.axis_index(mesh_axes[axis])
        else:
            n_dev, idx = 1, 0
        info[axis] = (idx, n_owned, n_owned * n_dev)
    return info


def _zero_boundary_strips(y: jnp.ndarray, haloed: jnp.ndarray, *, t: int,
                          r: int, base_core: Callable, spec_ndim: int,
                          mesh_axes: dict[int, str]) -> jnp.ndarray:
    """Splice per-step-clamped edge strips over the fused Dirichlet-0 output.

    Mirrors ``StencilEngine._zero_boundary_chunk`` on the deep-haloed local
    block: each spatial axis/side re-evolves a ``3*t*r``-deep slab by ``t``
    unfused valid steps, consuming the already-exchanged ``t*r`` halo on the
    other axes and clamping out-of-domain positions to zero before every
    step.  The resulting ``t*r``-wide strip is exact on EVERY device (away
    from the global edge the mask is a no-op and the trapezoid reproduces
    the fused values), so the splice needs no per-device branching.
    """
    nd = y.ndim
    lead = nd - spec_ndim
    w = t * r
    axinfo = _axis_info(y, spec_ndim, mesh_axes)
    for axis in range(lead, nd):
        n_own = y.shape[axis]
        h_ext = haloed.shape[axis]
        for side in (0, 1):
            sl = [slice(None)] * nd
            sl[axis] = slice(0, 3 * w) if side == 0 else slice(h_ext - 3 * w, h_ext)
            s = haloed[tuple(sl)]
            start = {a: -w for a in axinfo}
            start[axis] = -w if side == 0 else n_own - 2 * w
            for _ in range(t):
                s = base_core(_mask_outside_domain(s, start, axinfo))
                for a in start:
                    start[a] += r
            osl = [slice(None)] * nd
            osl[axis] = slice(0, w) if side == 0 else slice(n_own - w, n_own)
            y = y.at[tuple(osl)].set(s)
    return y


def distributed_fused_chunk(block: jnp.ndarray, *, t: int,
                            base_core: Callable, fused_core: Callable,
                            interior_core: Callable,
                            spec: StencilSpec, mesh_axes: dict[int, str],
                            periodic: bool = True,
                            overlap: bool = True) -> jnp.ndarray:
    """Advance a local block by ``t`` steps with ONE ``t*r`` halo exchange.

    The fused operator (order ``t*r``) is applied to the deep-haloed block;
    with ``overlap=True`` the halo-independent interior is expressed
    separately so XLA hides the permute latency behind interior MXU work.
    ``interior_core`` computes that interior: ``fused_core``'s arithmetic,
    its kernel named apart.
    Dirichlet-0 edge strips are fixed up per-step-exactly (``t > 1`` only —
    for a single step zero-extension IS per-step clamping).

    Requires ``block.shape[axis] >= t * spec.order`` on every spatial axis.
    """
    r = spec.order
    w = t * r
    nd_lead = block.ndim - spec.ndim
    for axis in range(nd_lead, block.ndim):
        if block.shape[axis] < w:
            raise ValueError(
                f"local block extent {block.shape[axis]} on axis {axis} is "
                f"smaller than the fused halo {w}; lower the fuse depth")

    haloed = _haloed_input(block, w, spec.ndim, mesh_axes, periodic)
    full = fused_core(haloed)

    if overlap and all(block.shape[a] > 2 * w for a in mesh_axes):
        # Interior: fused update from locally-available data only (sharded
        # halos stripped; unsharded axes keep their cheap local pads), exact
        # for points at distance >= t*r from the sharded local boundary.
        inner_in = _pad_local_axes(block, w, spec.ndim, mesh_axes, periodic)
        interior = interior_core(inner_in)  # shrinks SHARDED axes by 2*t*r
        index = [slice(None)] * block.ndim
        for axis in mesh_axes:
            index[axis] = slice(w, block.shape[axis] - w)
        full = full.at[tuple(index)].set(interior)

    if not periodic and t > 1:
        full = _zero_boundary_strips(full, haloed, t=t, r=r,
                                     base_core=base_core,
                                     spec_ndim=spec.ndim,
                                     mesh_axes=mesh_axes)
    return full


def distributed_stencil_step(block: jnp.ndarray, *, engine: StencilEngine,
                             mesh_axes: dict[int, str], periodic: bool = True,
                             overlap: bool = True) -> jnp.ndarray:
    """One sharded stencil step on a local block (inside shard_map).

    Single-step case of :func:`distributed_fused_chunk`; spatial axes left
    out of ``mesh_axes`` get their boundary applied locally instead of the
    (former) shape-mismatched splice.
    """
    if engine.plan.boundary != "valid":
        raise ValueError("distributed stepper needs a 'valid'-mode engine")
    core = engine._core
    return distributed_fused_chunk(block, t=1, base_core=core,
                                   fused_core=core, spec=engine.plan.spec,
                                   mesh_axes=mesh_axes, periodic=periodic,
                                   overlap=overlap,
                                   interior_core=engine.named_core(
                                       INTERIOR_STEP))


class DistributedStepper:
    """A compiled multi-device stepper plus its traceable building blocks.

    ``fn`` is the jitted sharded executable; ``global_fn`` is the un-jitted
    shard_map'd function (traceable with ``jax.make_jaxpr`` — the planner's
    acceptance test counts its ``ppermute`` equations); ``schedule`` is the
    static chunk schedule one call advances through.

    Calling the stepper routes through the HOST-side chaos wrapper: with
    a :class:`repro.runtime.chaos.FaultPlan` active, every call fires
    ``dist.device`` once and ``dist.chunk`` / ``dist.exchange`` once per
    fused chunk (firing indices are per-rule call counts — exact and
    replayable), then dispatches the SAME jitted executable.  With no
    plan active the wrapper is one global read; either way the compiled
    program (and its ppermute count per chunk) is untouched — host
    wrappers cannot appear in a jaxpr.
    """

    def __init__(self, fn: Callable, global_fn: Callable,
                 schedule: tuple[int, ...], mesh: Mesh, pspec: P,
                 radius: int = 1):
        self.fn = fn
        self.global_fn = global_fn
        self.schedule = tuple(schedule)
        self.mesh = mesh
        self.pspec = pspec
        self.radius = int(radius)

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        if chaos.active() is None:
            return self.fn(x)
        return self._chaos_call(x)

    def _chaos_call(self, x: jnp.ndarray) -> jnp.ndarray:
        """One stepper call with the mesh fault surface instrumented.

        ``raise`` kills the call before dispatch (a lost device / failed
        chunk launch); ``delay`` models a slow exchange or straggling
        device; ``corrupt`` (meaningful on ``dist.exchange``) models
        strips corrupted on the wire: the sweep runs to completion on a
        perturbed input — latency paid, result poisoned — then the
        transport checksum catches it and the call raises into the
        supervised retry path, discarding the poisoned result.
        """
        ctx = {"devices": self.n_devices,
               "mesh": "x".join(str(n) for n in self.mesh.devices.shape)}
        corrupted: tuple[str, int] | None = None
        if chaos.fire("dist.device", **ctx) == "corrupt":
            corrupted = ("dist.device", 0)
        for k, t in enumerate(self.schedule):
            if chaos.fire("dist.chunk", chunk=k, depth=int(t),
                          **ctx) == "corrupt" and corrupted is None:
                corrupted = ("dist.chunk", k)
            if chaos.fire("dist.exchange", chunk=k,
                          width=int(t * self.radius),
                          **ctx) == "corrupt" and corrupted is None:
                corrupted = ("dist.exchange", k)
        if corrupted is not None:
            site, k = corrupted
            jax.block_until_ready(self.fn(x + jnp.ones((), x.dtype)))
            raise FaultError(site, k, "corrupted halo strips detected "
                                      "(transport checksum)")
        return self.fn(x)


def make_fused_distributed_stepper(spec: StencilSpec, mesh: Mesh,
                                   grid_axes: Sequence[str], *,
                                   schedule: Sequence[int],
                                   option: str = "auto",
                                   fused_option: str = "auto",
                                   backend: str = "jnp",
                                   boundary: str = "periodic",
                                   block: tuple[int, ...] | None = None,
                                   fuse_strategy: str = "operator",
                                   batch: int | None = None,
                                   overlap: bool = True) -> DistributedStepper:
    """Build the fused multi-device sweep: one ``t*r`` exchange per chunk.

    ``schedule`` is the static list of chunk depths (e.g. ``[4, 4, 2]`` for
    10 steps at fuse depth 4) — the planner's ``ExecutionPlan.fuse_schedule``
    feeds straight in.  ``fused_option`` pins the cover of the deepest fused
    operator (remainder chunks re-cover automatically).

    ``fuse_strategy="inkernel"`` swaps every depth-t chunk core for the
    backend's in-kernel temporal-blocking sweep (T base steps per kernel
    instance, VMEM-resident intermediates).  The exchange protocol is
    untouched: the in-kernel core consumes exactly the same ``t*r``-deep
    haloed block the fused operator would, so it still costs ONE exchange
    per chunk, and the Dirichlet-0 strips re-evolve through the same
    unfused base core.

    ``batch`` adds a leading replicated batch axis of that extent: B
    independent states advance through the same schedule in one call.
    Batched states are spatially independent, so the halo layer and the
    exchange protocol are untouched — each chunk still issues exactly ONE
    ``t*r``-deep exchange (the ppermuted strips simply carry a batch
    axis), and the chunk cores fold the batch into their MXU contractions.
    """
    if boundary not in ("periodic", "zero"):
        raise ValueError("distributed sweeps need boundary='periodic'|'zero'")
    if fuse_strategy not in temporal.FUSE_STRATEGIES:
        raise ValueError(f"unknown fuse strategy {fuse_strategy!r}; choose "
                         f"from {temporal.FUSE_STRATEGIES}")
    schedule = tuple(int(t) for t in schedule)
    if any(t < 1 for t in schedule):
        raise ValueError(f"chunk depths must be >= 1, got {schedule}")
    if batch is not None and batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    periodic = boundary == "periodic"

    base = StencilEngine(spec, option=option, backend=backend, block=block,
                         boundary="valid")
    depth_max = max(schedule) if schedule else 1
    # per depth, the chunk's core over the haloed block and the interior
    # recompute's core: the same arithmetic, the kernel named apart
    cores: dict[int, Callable] = {1: base._core}
    interiors: dict[int, Callable] = {1: base.named_core(INTERIOR_STEP)}
    for t in sorted(set(schedule)):
        if t > 1:
            if fuse_strategy == "inkernel":
                cores[t] = base.inkernel_core(t)
                interiors[t] = base.inkernel_core(t, name=INTERIOR_SWEEP)
                continue
            opt = fused_option if t == depth_max else "auto"
            fused = StencilEngine(temporal.fuse_steps(spec, t), option=opt,
                                  backend=backend, block=base.plan.block,
                                  boundary="valid")
            cores[t] = fused._core
            interiors[t] = fused.named_core(INTERIOR_STEP)

    grid_axes = tuple(grid_axes)
    lead = 0 if batch is None else 1
    # mesh_axes keys are ARRAY axes: spatial index + the batch lead offset
    mesh_axes = {i + lead: ax for i, ax in enumerate(grid_axes) if ax}
    pspec = P(*([None] * lead + [ax if ax else None for ax in grid_axes]))

    def local_fn(b):
        for t in schedule:
            b = distributed_fused_chunk(b, t=t, base_core=cores[1],
                                        fused_core=cores[t], spec=spec,
                                        mesh_axes=mesh_axes,
                                        periodic=periodic, overlap=overlap,
                                        interior_core=interiors[t])
        return b

    sharded = jax.shard_map(local_fn, mesh=mesh, in_specs=pspec,
                            out_specs=pspec, check_vma=False)
    fn = jax.jit(sharded,
                 in_shardings=NamedSharding(mesh, pspec),
                 out_shardings=NamedSharding(mesh, pspec))
    return DistributedStepper(fn, sharded, schedule, mesh, pspec,
                              radius=spec.order)


def make_distributed_stepper(spec: StencilSpec, mesh: Mesh,
                             grid_axes: tuple[str, ...],
                             option: str = "auto", backend: str = "jnp",
                             periodic: bool = True, overlap: bool = True,
                             steps: int = 1) -> Callable[[jnp.ndarray], jnp.ndarray]:
    """Build a jit-ted multi-device stencil stepper (width-r exchange/step).

    ``grid_axes``: mesh axis name for each spatial array axis (use '' to
    leave an axis unsharded — its boundary is then applied locally). The
    returned fn maps a global array sharded as P(*grid_axes) to the evolved
    global array.  Kept as the simple per-step API; fused multi-step sweeps
    go through :func:`make_fused_distributed_stepper` / ``repro.api``.
    """
    engine = StencilEngine(spec, option=option, backend=backend, boundary="valid")
    mesh_axes = {i: ax for i, ax in enumerate(grid_axes) if ax}
    pspec = P(*[ax if ax else None for ax in grid_axes])

    def local_step(block):
        return distributed_stencil_step(block, engine=engine, mesh_axes=mesh_axes,
                                        periodic=periodic, overlap=overlap)

    def global_step(x):
        return lax.fori_loop(0, steps, lambda _, a: sharded(a), x) if steps > 1 else sharded(x)

    sharded = jax.shard_map(local_step, mesh=mesh, in_specs=pspec,
                            out_specs=pspec, check_vma=False)
    return jax.jit(global_step,
                   in_shardings=NamedSharding(mesh, pspec),
                   out_shardings=NamedSharding(mesh, pspec))
