"""Pure-jnp gather-mode stencil oracles (independent of the matrixized path).

These are the reference semantics every kernel and every matrixized
evaluation is checked against: the textbook Eq. 1 gather loop, written as
shifted-slab accumulation so it stays a single fused XLA computation.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from repro.core import halo
from repro.core.stencil_spec import StencilSpec

__all__ = ["stencil_ref", "stencil_ref_conv", "banded_mixer_ref",
           "scenario_scale", "REORDER_TOL"]

#: Agreement bar for two executions of the SAME per-state arithmetic whose
#: contractions may sum in a different order: a batch folded into one
#: ``dot_general`` vs ``jax.vmap`` of the single-state call, an in-kernel
#: sweep vs the per-step sequence, a recomputed (retried or resharded)
#: result vs the first one.  XLA:CPU and the TPU's MXU each choose the
#: reduction order per operand shape, so such results agree to a few f32
#: ulps, not to the bit.
REORDER_TOL = dict(rtol=1e-6, atol=1e-6)


def scenario_scale(acc: jnp.ndarray, spec: StencilSpec, ndim: int,
                   accum_dtype=jnp.float32) -> jnp.ndarray:
    """Apply a spec's scenario fields to a valid-mode f32 accumulator.

    ``y = M * (a * acc)`` with the coefficient field ``a`` and domain mask
    ``M`` CENTER-sliced to the accumulator's spatial extent (offset
    ``(field_extent - out_extent) // 2`` per axis — the positional
    convention every execution path and this oracle share, so parity stays
    bit-exact).  No-op for constant unmasked specs.
    """
    out_spatial = acc.shape[acc.ndim - ndim:]

    def center(field):
        f = np.asarray(field)
        idx = []
        for a, m in enumerate(out_spatial):
            off = (f.shape[a] - m) // 2
            if off < 0:
                raise ValueError(
                    f"scenario field extent {f.shape} smaller than output "
                    f"extent {out_spatial}")
            idx.append(slice(off, off + m))
        return f[tuple(idx)]

    if spec.is_varying:
        acc = acc * jnp.asarray(center(spec.coeff_field), accum_dtype)
    if spec.is_masked:
        acc = acc * jnp.asarray(center(spec.domain_mask), accum_dtype)
    return acc


def stencil_ref(x: jnp.ndarray, spec: StencilSpec, accum_dtype=jnp.float32,
                boundary: str = "valid") -> jnp.ndarray:
    """Gather stencil oracle: ``B[p] = sum_o Cg[o] * A[p + o]``.

    Leading axes beyond ``spec.ndim`` are batch axes.  ``boundary`` follows
    the shared halo layer: 'valid' shrinks by ``spec.order`` per side;
    'zero'/'periodic' are shape-preserving.  Varying-coefficient and masked
    specs scale the accumulated sum per point (``y = M * (a * sum)``, f32,
    before the output cast) — gather-mode ground truth for the scenario
    paths too.
    """
    ndim, r = spec.ndim, spec.order
    x = halo.pad_halo(x, r, ndim, boundary)
    lead_n = x.ndim - ndim
    cg = np.asarray(spec.gather_coeffs)
    out = None
    for off in np.ndindex(*cg.shape):
        c = cg[off]
        if c == 0.0:
            continue
        index = [slice(None)] * x.ndim
        for a_sp, o in enumerate(off):
            a = a_sp + lead_n
            index[a] = slice(o, o + x.shape[a] - 2 * r)
        term = jnp.asarray(c, accum_dtype) * x[tuple(index)].astype(accum_dtype)
        out = term if out is None else out + term
    out = scenario_scale(out, spec, ndim, accum_dtype)
    return out.astype(x.dtype)


def stencil_ref_conv(x: jnp.ndarray, spec: StencilSpec) -> jnp.ndarray:
    """Same semantics via ``lax.conv_general_dilated`` (XLA's native conv).

    Used as the 'compiler vectorized' baseline in benchmarks and as a second
    independent oracle. 2-D / 3-D, single feature channel, batch-leading.
    """
    from jax import lax

    ndim, r = spec.ndim, spec.order
    lead = x.shape[: x.ndim - ndim]
    spatial = x.shape[x.ndim - ndim:]
    xb = x.reshape((-1, 1) + spatial)  # N, C=1, spatial...
    # Correlation == conv with reversed kernel; conv_general_dilated computes
    # correlation when we pass the kernel unreversed with default dim numbers?
    # XLA convolution is true convolution-less: it computes correlation.
    k = jnp.asarray(spec.gather_coeffs, x.dtype).reshape((1, 1) + spec.gather_coeffs.shape)
    dn = lax.conv_dimension_numbers(xb.shape, k.shape,
                                    ("NC" + "DHW"[-ndim:], "OI" + "DHW"[-ndim:],
                                     "NC" + "DHW"[-ndim:]))
    out = lax.conv_general_dilated(xb, k, window_strides=(1,) * ndim,
                                   padding="VALID", dimension_numbers=dn)
    return out.reshape(lead + out.shape[2:]).astype(x.dtype)


def banded_mixer_ref(x: jnp.ndarray, band: jnp.ndarray) -> jnp.ndarray:
    """Causal banded sequence mixer oracle.

    ``y[t] = sum_{s=0}^{W-1} band[s] * x[t - s]`` with zero history
    (x: (..., T, D), band: (W,) shared across channels).  This is the 1-D
    causal stencil the LM stack consumes (token-shift / short conv).
    """
    w = band.shape[0]
    acc = None
    for s in range(w):
        shifted = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(s, 0), (0, 0)])[..., : x.shape[-2], :]
        term = band[s] * shifted
        acc = term if acc is None else acc + term
    return acc.astype(x.dtype)
