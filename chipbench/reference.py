"""Plain reference for the stencil cells, and its lower-precision control.

It imports nothing of the program under test: the operator is the gather
form of the stencil, ``y[p] = sum_o c[o] * x[p + o - r]`` with periodic
wrap, summed in float32 over shifted slices of the state on its trailing
``ndim`` axes (leading axes are independent states).  The coefficients
come from the configuration file.  A state sharded over a mesh is evolved
shard by shard, each shard padded with ``r`` rows of its neighbours'
edges, so the reference fits where the program's state does.

``precision="highest"`` multiplies in float32, as the configurations
state.  ``precision="high"`` is the control: each product is taken the
way a three-pass bf16 contraction (``Precision.HIGH``) takes it,
``a*b ~ ah*bh + ah*bl + al*bh`` with ``a = ah + al`` split into two
bfloat16 parts and the ``al*bl`` term dropped.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding

PRECISIONS = ("highest", "high")


def taps(coeffs) -> list[tuple[tuple[int, ...], float]]:
    """Non-zero ``(offset, coefficient)`` pairs, offsets relative to the
    output point, of a gather coefficient tensor of extent ``2r+1``."""
    c = np.asarray(coeffs, np.float64)
    r = (c.shape[0] - 1) // 2
    return [(tuple(int(i) - r for i in idx), float(c[idx]))
            for idx in zip(*np.nonzero(c))]


def _bf16(v):
    """``v`` rounded to bfloat16 and kept in float32.  ``reduce_precision``
    is an op the compiler has to keep; the TPU compiler may fold a round
    trip through ``astype`` away in part (on box3d_r1 at 512^3 the control
    then read 4x the program, or a whole bf16 rounding, by how the sum
    was written)."""
    return lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _split_bf16(v):
    hi = _bf16(v)
    return hi, _bf16(v - hi)


def _split_coeff(c: float) -> tuple[float, float]:
    """The coefficient's two bfloat16 parts, on the host: constants."""
    hi = np.float32(np.float32(c).astype(jnp.bfloat16))
    lo = np.float32(np.float32(np.float32(c) - hi).astype(jnp.bfloat16))
    return float(hi), float(lo)


def _valid(xp, tp, r: int, precision: str):
    """Gather sum over a state padded by ``r`` on its trailing axes."""
    if precision == "high":
        return _valid_high(xp, tp, r)
    nd = len(tp[0][0])
    axes = range(xp.ndim - nd, xp.ndim)
    out = None
    for off, c in tp:
        idx = [slice(None)] * xp.ndim
        for a, o in zip(axes, off):
            idx[a] = slice(r + o, xp.shape[a] - r + o)
        term = jnp.float32(c) * xp[tuple(idx)]
        out = term if out is None else out + term
    return out


def _valid_high(xp, tp, r: int):
    """The gather sum at ``high``: the padded state is split once, and a
    loop adds one tap a turn, in the same order, so that one partial sum
    is live (an unrolled sum over both parts of 27 taps does not fit a
    512^3 state on one chip)."""
    nd = len(tp[0][0])
    lead = xp.ndim - nd
    size = xp.shape[:lead] + tuple(n - 2 * r for n in xp.shape[lead:])
    xh, xl = _split_bf16(xp)
    offs = jnp.asarray([[r + o for o in off] for off, _ in tp], jnp.int32)
    cs = jnp.asarray([_split_coeff(c) for _, c in tp], jnp.float32)

    def tap(i, out):
        start = (0,) * lead + tuple(offs[i, k] for k in range(nd))
        h = lax.dynamic_slice(xh, start, size)
        lo = lax.dynamic_slice(xl, start, size)
        return out + (cs[i, 0] * h + cs[i, 0] * lo + cs[i, 1] * h)
    return lax.fori_loop(0, len(tp), tap, jnp.zeros(size, jnp.float32))


def _edges(b, r: int, a: int, name: str | None, n: int):
    """``b`` padded by ``r`` along axis ``a`` with the periodic
    neighbours' edges: its own (one shard on the axis) or, through
    ``ppermute``, those of the shards before and after it on mesh axis
    ``name``."""
    lo = lax.slice_in_dim(b, 0, r, axis=a)
    hi = lax.slice_in_dim(b, b.shape[a] - r, b.shape[a], axis=a)
    if name is not None and n > 1:
        hi = lax.ppermute(hi, name, [(i, (i + 1) % n) for i in range(n)])
        lo = lax.ppermute(lo, name, [(i, (i - 1) % n) for i in range(n)])
    return jnp.concatenate([hi, b, lo], axis=a)


def _local_step(b, tp, r: int, precision: str, names, sizes):
    nd = len(tp[0][0])
    b = b.astype(jnp.float32)
    for k, name in enumerate(names):
        a = b.ndim - nd + k
        b = _edges(b, r, a, name, sizes.get(name, 1))
    return _valid(b, tp, r, precision)


@functools.lru_cache(maxsize=None)
def _evolve_fn(coeffs_key, steps: int, precision: str, sharding):
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    coeffs = np.asarray(coeffs_key[1]).reshape(coeffs_key[0])
    tp = taps(coeffs)
    r = (coeffs.shape[0] - 1) // 2
    nd = len(tp[0][0])
    if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
        spec = tuple(sharding.spec) + (None,) * (nd - len(sharding.spec))
        names = spec[len(spec) - nd:]
        sizes = dict(sharding.mesh.shape)

        def body(b):
            return lax.fori_loop(0, steps, lambda _, a: _local_step(
                a, tp, r, precision, names, sizes), b)
        run = jax.shard_map(body, mesh=sharding.mesh, in_specs=sharding.spec,
                            out_specs=sharding.spec, check_vma=False)
        return jax.jit(run, in_shardings=sharding, out_shardings=sharding)

    def run(x):
        return lax.fori_loop(0, steps, lambda _, a: _local_step(
            a, tp, r, precision, (None,) * nd, {}), x)
    return jax.jit(run)


def evolve(x, coeffs, steps: int, precision: str = "highest",
           sharding=None):
    """``steps`` applications of the stencil in one jitted loop; a state
    with a mesh ``sharding`` is evolved shard by shard and stays sharded."""
    c = np.asarray(coeffs, np.float64)
    key = (c.shape, tuple(c.ravel().tolist()))
    return _evolve_fn(key, int(steps), precision, sharding)(x)


@jax.jit
def _gap(y, ref):
    ref = ref.astype(jnp.float32)
    return (jnp.max(jnp.abs(y.astype(jnp.float32) - ref)),
            jnp.max(jnp.abs(ref)))


def rel_err(y, ref) -> float:
    """Largest gap to the reference over the largest reference value."""
    gap, scale = _gap(y, ref)
    return float(gap) / max(float(scale), float(np.finfo(np.float32).tiny))
