"""Pallas TPU kernel: fused causal flash attention (forward).

The §Perf analysis (EXPERIMENTS.md iter 3) shows the pure-JAX attention
floor is ~3 HBM passes over the S x S score tiles; this kernel is the TPU
deployment answer — scores never leave VMEM.  Grid: (batch*heads, q
blocks); the kernel body scans KV blocks with the online-softmax update,
accumulating in VMEM scratch.  Mirrors the stencil kernel's scheduling
(paper observation 1/3): output block stationary, inputs streamed.

Validated in interpret mode against the dense oracle
(`tests/test_flash_kernel.py`); the SPMD dry-run keeps the jnp path
because interpret-mode grid loops defeat the GSPMD partitioner
(DESIGN.md §8) — on real TPU hardware this kernel replaces it.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
import jax.experimental.pallas as pl

from repro.kernels.stencil_mxu import resolve_interpret

__all__ = ["flash_attention_pallas", "flash_attention"]

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, block_q, block_k, seq_len, scale,
            causal):
    qi = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # (block_q, dh)
    m = jnp.full((block_q,), NEG, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q_ref.shape[-1]), jnp.float32)
    q_pos = qi * block_q + jnp.arange(block_q)

    nk = seq_len // block_k
    for kj in range(nk):                                 # unrolled KV walk
        k_blk = k_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(kj * block_k, block_k), :].astype(jnp.float32)
        s = q @ k_blk.T                                  # (block_q, block_k)
        if causal:
            k_pos = kj * block_k + jnp.arange(block_k)
            msk = k_pos[None, :] <= q_pos[:, None]
            s = jnp.where(msk, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + p @ v_blk
        m = m_new
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, block_q: int = 128, block_k: int = 128,
                           causal: bool = True, interpret: bool | None = None):
    """q/k/v: (B, H, S, Dh) with S % block == 0. Returns (B, H, S, Dh)."""
    b, h, s, dh = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"S={s} must be a multiple of the blocks")
    scale = 1.0 / np.sqrt(dh)
    bh = b * h
    qf = q.reshape(bh, s, dh)
    kf = k.reshape(bh, s, dh)
    vf = v.reshape(bh, s, dh)

    kernel = functools.partial(_kernel, block_q=block_q, block_k=block_k,
                               seq_len=s, scale=scale, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=(bh, s // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, dh), lambda g, i: (g, i, 0)),
            pl.BlockSpec((None, s, dh), lambda g, i: (g, 0, 0)),
            pl.BlockSpec((None, s, dh), lambda g, i: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, dh), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        interpret=resolve_interpret(interpret),
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, h, s, dh)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret: bool | None = None):
    """Differentiable wrapper: Pallas forward, dense-oracle backward.

    The backward pass recomputes probabilities densely (one S x S tile per
    (b, h)) — correct and simple; a fused Pallas backward is the standard
    next step on hardware.
    """
    return flash_attention_pallas(q, k, v, causal=causal, interpret=interpret)


def _dense(q, k, v, causal):
    dh = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(dh)
    if causal:
        n = q.shape[2]
        msk = jnp.tril(jnp.ones((n, n), bool))
        s = jnp.where(msk, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return p, jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def _fwd(q, k, v, causal, interpret):
    return flash_attention_pallas(q, k, v, causal=causal,
                                  interpret=interpret), (q, k, v)


def _bwd(causal, interpret, res, g):
    q, k, v = res
    p, o = _dense(q, k, v, causal)
    g = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, g)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g, v.astype(jnp.float32))
    delta = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - delta) / np.sqrt(q.shape[-1])
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention.defvjp(_fwd, _bwd)
