"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Dispatch is the MaxText/GShard-style dense formulation that shards cleanly:
tokens are scattered into an (E, C, D) buffer (position-in-expert computed
by a stable sort over expert assignments), expert FFNs run as one batched
einsum over E (expert-parallel over the 'model'/'expert' mesh axis), and
results gather back with router gates.  Overflow beyond capacity C drops
(standard capacity-factor semantics); an auxiliary load-balance loss keeps
the router honest.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init
from repro.sharding.rules import axis_size, current_mesh, shard

__all__ = ["init_moe", "moe_ffn", "MoEOut"]


class MoEOut(NamedTuple):
    y: jnp.ndarray
    aux_loss: jnp.ndarray


def init_moe(key, d: int, mcfg, dtype=jnp.float32):
    kr, kg, ku, ko = jax.random.split(key, 4)
    e, dff = mcfg.num_experts, mcfg.d_ff_expert
    s_in = 1.0 / np.sqrt(d)
    s_out = 1.0 / np.sqrt(dff)
    return {
        "router": dense_init(kr, d, e, dtype),
        "wi_gate": (jax.random.normal(kg, (e, d, dff), jnp.float32) * s_in).astype(dtype),
        "wi_up": (jax.random.normal(ku, (e, d, dff), jnp.float32) * s_in).astype(dtype),
        "wo": (jax.random.normal(ko, (e, dff, d), jnp.float32) * s_out).astype(dtype),
    }


def moe_ffn(p, x, mcfg, act: str = "silu", dropless: bool = False) -> MoEOut:
    """x: (B, S, D) -> (B, S, D). Top-k routed expert SwiGLU.

    ``dropless=True`` sets capacity to the exact upper bound (serving path:
    decode must agree with the train-mode forward bit-for-bit when nothing
    drops there either).

    ``mcfg.groups > 1`` dispatches per token-group (MaxText-style: one group
    per data shard) so position-in-expert needs no global sort — dispatch
    stays shard-local and the (G, E, C, D) buffer shards over (dp, expert).
    """
    b, s, d = x.shape
    t_all = b * s
    g = mcfg.groups if (mcfg.groups and t_all % mcfg.groups == 0) else 1
    xg = shard(x.reshape(g, t_all // g, d), "dp", None, None)

    # EP fast path (Perf iter 4): when the expert count divides the model
    # axis, dispatch under shard_map — every model shard runs the (cheap,
    # replicated) router, locally selects assignments for ITS experts, and
    # the only cross-shard traffic is ONE psum of the (T, D) combine.  The
    # jit/GSPMD formulation of the same dispatch all-gathers the whole
    # (E, C, D) buffer per layer (measured ~20 GB/layer on qwen3 train_4k).
    tp = axis_size("tp")
    mesh = current_mesh()
    if mesh is not None and tp > 1 and mcfg.num_experts % tp == 0 \
            and "model" in mesh.axis_names:
        from jax.sharding import PartitionSpec as P

        def local_fn(xg_l, router, wig, wiu, wo):
            xg_l = xg_l.astype(x.dtype)
            nsh = jax.lax.axis_size("model")
            midx = jax.lax.axis_index("model")
            e_loc = mcfg.num_experts // nsh
            p_l = {"router": router, "wi_gate": wig, "wi_up": wiu, "wo": wo}
            core = functools.partial(_moe_group, p=p_l, mcfg=mcfg, act=act,
                                     dropless=dropless,
                                     local_range=(midx * e_loc, e_loc))
            y_part, aux = jax.vmap(core)(xg_l)
            # f32 psum: XLA-CPU's AllReducePromotion pass CHECK-crashes on
            # bf16 all-reduce here; on TPU flip this back to bf16 wire
            y_sum = jax.lax.psum(y_part.astype(jnp.float32), "model")
            # aux is identical on every shard (global routing); average so
            # the output is *provably* replicated (avoids the copy-reduction
            # all-reduce XLA-CPU can't retype)
            aux = jax.lax.pmean(aux, "model")
            return y_sum.astype(y_part.dtype), aux

        y, aux = jax.shard_map(
            local_fn,
            mesh=mesh,
            in_specs=(P(), P(), P("model"), P("model"), P("model")),
            out_specs=(P(), P()),
            axis_names={"model"},
            check_vma=False,
        )(xg.astype(jnp.float32),  # f32 boundary: the implicit input-
          # cotangent psum must not be bf16 (XLA-CPU AllReducePromotion bug)
          p["router"], p["wi_gate"], p["wi_up"], p["wo"])
    else:
        core = functools.partial(_moe_group, p=p, mcfg=mcfg, act=act,
                                 dropless=dropless)
        y, aux = jax.vmap(core)(xg)
    y = shard(y, "dp", None, None)
    return MoEOut(y=y.reshape(b, s, d), aux_loss=jnp.mean(aux))


def _moe_group(xt, *, p, mcfg, act, dropless, local_range=None):
    """One dispatch group. ``local_range=(lo, n)`` restricts compute to the
    n experts starting at ``lo`` (EP shard_map path); routing and positions
    are computed globally (identical on every shard) so capacity semantics
    match the single-device path exactly."""
    t, d = xt.shape
    e, k = mcfg.num_experts, mcfg.top_k

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, k)            # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # Load-balance aux loss (Switch): E * sum_e f_e * p_e
    density = jnp.mean(jax.nn.one_hot(experts[:, 0], e), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * mean_probs) * mcfg.aux_loss_weight

    # -- sort-based position-in-expert ------------------------------------
    if dropless:
        cap = t  # exact bound: top-k experts are distinct per token
    else:
        cap = int(np.ceil(t * k / e * mcfg.capacity_factor))
        cap = max(min(cap, t * k), 1)
    flat_expert = experts.reshape(-1)                   # (T*k,)
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_gate = gates.reshape(-1)

    order = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[order]
    # position within expert group = index - start_of_group
    counts = jnp.bincount(flat_expert, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_sorted = jnp.arange(t * k) - starts[sorted_expert]
    pos = jnp.zeros(t * k, jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))

    keep = pos < cap
    if local_range is not None:
        lo, n_loc = local_range
        keep = keep & (flat_expert >= lo) & (flat_expert < lo + n_loc)
        flat_expert = jnp.clip(flat_expert - lo, 0, n_loc - 1)
        e = n_loc
    safe_pos = jnp.where(keep, pos, cap - 1)

    # scatter tokens -> (E, C, D); dropped tokens contribute zero
    buf = jnp.zeros((e, cap, d), xt.dtype)
    contrib = jnp.where(keep[:, None], xt[flat_token], 0.0)
    buf = buf.at[flat_expert, safe_pos].add(contrib)

    # expert FFN: batched over E (EP shards this einsum on the expert axis;
    # sharding propagates from the expert-sharded weights)
    g = jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"].astype(xt.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"].astype(xt.dtype))
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g, approximate=True)
    eo = jnp.einsum("ecf,efd->ecd", a * u, p["wo"].astype(xt.dtype))

    # gather back with gates (non-kept/non-local assignments contribute 0)
    out_flat = eo[flat_expert, safe_pos]                # (T*k, D)
    out_flat = jnp.where(keep[:, None], out_flat, 0.0) * flat_gate[:, None].astype(xt.dtype)
    y = jnp.zeros((t, d), xt.dtype).at[flat_token].add(out_flat)
    return y, aux.astype(jnp.float32)
