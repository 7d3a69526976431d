"""Pallas TPU kernel: causal banded sequence mixer.

The LM-stack instantiation of stencil matrixization (DESIGN.md §2/§5): a
1-D causal constant-band stencil over a (seq, d) slab — token-shift, short
convolution, local mixing.  On SME the paper rules 1-D stencils out (input
vectors must span two directions); on TPU the channel axis supplies the
second direction and the whole update is one banded-Toeplitz matmul per
sequence tile:

    y[t, :] = sum_{s<W} band[s] * x[t-s, :]     ==    T @ x_slab

Shared-band mode runs on the MXU; per-channel (depthwise) mode is the
paper's degenerate single-nonzero-line case and runs as W unrolled VPU
scaled shifts inside the same kernel.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import stencil_mxu as smx

__all__ = ["banded_mixer_pallas_call"]


def _shared_kernel(block, out_dtype):
    def kernel(x_hbm, t_ref, o_ref, win, sem):
        smx._copy_window(x_hbm, win, sem, block)
        slab = win[...].astype(jnp.float32)        # (>= bt + w - 1, >= bd)
        t = t_ref[...]                             # (bt, win rows)
        acc = jax.lax.dot_general(
            t, slab, dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = acc[:, :block[1]].astype(out_dtype)
    return kernel


def _depthwise_kernel(w: int, block, out_dtype):
    bt, bd = block

    def kernel(x_hbm, band_ref, o_ref, win, sem):
        smx._copy_window(x_hbm, win, sem, block)
        slab = win[...].astype(jnp.float32)        # (>= bt + w - 1, >= bd)
        band = band_ref[...].astype(jnp.float32)   # (w, bd)
        acc = jnp.zeros((bt, bd), jnp.float32)
        for s in range(w):                         # degenerate lines: VPU
            acc = acc + band[s][None, :] * slab[w - 1 - s: w - 1 - s + bt,
                                                :bd]
        o_ref[...] = acc.astype(out_dtype)
    return kernel


def banded_mixer_pallas_call(x: jnp.ndarray, band: jnp.ndarray,
                             block_t: int = 128, block_d: int = 128,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Causal banded mix of a (T, D) slab with zero history.

    band: (W,) shared across channels (MXU path) or (W, D) depthwise
    (degenerate VPU path).  T, D must be multiples of the blocks (ops pads).
    """
    t_len, d = x.shape
    w = band.shape[0]
    if t_len % block_t or d % block_d:
        raise ValueError(f"(T={t_len}, D={d}) not multiples of block "
                         f"({block_t}, {block_d})")
    grid = (t_len // block_t, d // block_d)
    block = (block_t, block_d)
    # each instance DMAs its overlapping (block_t + W - 1, block_d) window,
    # rounded up to the VMEM tile (the stencil kernels' layout); zero
    # history: W - 1 rows in front of time, the tail padded so the last
    # rounded window is in bounds
    win = smx._window((block_t + w - 1, block_d), x.dtype)
    xp = jnp.pad(x, ((w - 1, (grid[0] - 1) * block_t + win[0] - t_len - w + 1),
                     (0, (grid[1] - 1) * block_d + win[1] - d)))

    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if band.ndim == 1:
        # T[p, p + u] = band[w - 1 - u]  (gather band reversed; see module
        # doc); the columns past block_t + W - 1 meet the window's padding
        tt = np.zeros((block_t, win[0]), np.float32)
        rows = np.arange(block_t)
        bb = np.asarray(band, np.float64)
        for u in range(w):
            tt[rows, rows + u] = bb[w - 1 - u]
        const = jnp.asarray(tt)
        in_specs.append(pl.BlockSpec(tt.shape, lambda i, j: (0, 0)))
        kernel = _shared_kernel(block, x.dtype)
    else:
        const = jnp.asarray(band, jnp.float32)
        in_specs.append(pl.BlockSpec((w, block_d), lambda i, j: (0, j)))
        kernel = _depthwise_kernel(w, block, x.dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(block, lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t_len, d), x.dtype),
        scratch_shapes=[pltpu.VMEM(win, x.dtype),
                        pltpu.SemaphoreType.DMA(())],
        interpret=smx.resolve_interpret(interpret),
    )(xp, const)
