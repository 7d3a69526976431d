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


def _split_bf16(v):
    hi = v.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (v - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _product(c: float, x, precision: str):
    c32 = jnp.float32(c)
    if precision == "highest":
        return c32 * x
    ch, cl = _split_bf16(c32)
    xh, xl = _split_bf16(x)
    return ch * xh + ch * xl + cl * xh


def _valid(xp, tp, r: int, precision: str):
    """Gather sum over a state padded by ``r`` on its trailing axes."""
    nd = len(tp[0][0])
    axes = range(xp.ndim - nd, xp.ndim)
    out = None
    for off, c in tp:
        idx = [slice(None)] * xp.ndim
        for a, o in zip(axes, off):
            idx[a] = slice(r + o, xp.shape[a] - r + o)
        term = _product(c, xp[tuple(idx)], precision)
        out = term if out is None else out + term
    return out


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
