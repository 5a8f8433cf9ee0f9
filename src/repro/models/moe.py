"""Mixture-of-Experts FFN with expert parallelism over the model axis.

Dispatch strategy (sort-based, no O(T*E*C) one-hot tensors):
activations enter the MoE replicated across the model axis (the same
layout TP gives the dense FFN), so every model shard routes *all* of its
data-shard's tokens, keeps only the slots owned by its local experts,
builds a static-capacity [E_local, C, d] buffer via a stable sort, runs
the expert matmuls, scatters back, and psums across the model axis —
one all-reduce, the same collective the dense TP FFN needs, and all
routing/sort work is shard-local (no global argsort collectives).

Token slots beyond an expert's capacity are dropped (standard static
-capacity semantics); Runtime.capacity_factor scales C (tests use a
large factor to verify the dropless limit equals the dense reference).
That is the training path. Serving (``dropless=True``: prefill and
decode) drops nothing: the router picks top_k of all n_experts, then
softmax over the picked logits (the same weights as softmax, top-k and
renormalise); the assignments to the experts this layer holds
(MoEConfig.held_offset, .held) are sorted by expert and run as one
grouped matmul per projection (ops.grouped_matmul: megablox ``gmm`` on
the TPU), and the rest are left to the chips that hold those experts.

A shared expert (MoEConfig.shared_d_ff, granite) is a SwiGLU MLP that
every token runs, added once beside the routed experts.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.models import common
from repro.parallel.sharding import shard_map
from repro.models.common import Runtime


def init_moe(key, cfg, dtype):
    """Router over all n_experts (fp32); the held experts' weights."""
    from repro.models import mlp
    m = cfg.moe
    d, ff, e = cfg.d_model, m.d_ff, m.held
    ks = jax.random.split(key, 4)
    std = 1.0 / math.sqrt(d)
    params = {
        "router": common.init_dense(ks[0], d, m.n_experts, jnp.float32),
        "wg": (jax.random.normal(ks[1], (e, d, ff), jnp.float32) * std).astype(dtype),
        "wu": (jax.random.normal(ks[2], (e, d, ff), jnp.float32) * std).astype(dtype),
        "wd": (jax.random.normal(ks[3], (e, ff, d), jnp.float32)
               * (1.0 / math.sqrt(ff))).astype(dtype),
    }
    if m.shared_d_ff:
        params["shared"] = mlp.init_mlp(jax.random.fold_in(key, 4), cfg,
                                        dtype, d_ff=m.shared_d_ff)
    return params


def moe_specs(cfg):
    from repro.models import mlp
    specs = {
        "router": P(None, None),
        "wg": P("model", None, None),
        "wu": P("model", None, None),
        "wd": P("model", None, None),
    }
    if cfg.moe.shared_d_ff:
        specs["shared"] = mlp.mlp_specs(cfg)
    return specs


def _capacity(tokens: int, n_experts: int, top_k: int, factor: float) -> int:
    c = int(math.ceil(tokens * top_k / n_experts * factor))
    return max(4, min(c, tokens * top_k))


def _moe_local(x, router, wg, wu, wd, *, cfg, rt: Runtime, tp_axis: str,
               dp_axes: Tuple[str, ...], capacity: int):
    """Per-shard MoE body (runs under shard_map).
    x [Tl, d] local tokens; wg/wu/wd local expert slices [El, d|ff, ...]."""
    m = cfg.moe
    tl, d = x.shape
    el = wg.shape[0]
    k = m.top_k
    cd = rt.compute_dtype

    gates = jax.nn.softmax((x.astype(jnp.float32) @ router), axis=-1)  # [Tl,E]
    topv, topi = jax.lax.top_k(gates, k)                               # [Tl,k]
    topv = topv / jnp.maximum(topv.sum(axis=-1, keepdims=True), 1e-9)

    e0 = m.held_offset + jax.lax.axis_index(tp_axis) * el
    flat_e = topi.reshape(-1)                                          # [Tl*k]
    flat_w = topv.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(tl), k)
    local_e = flat_e - e0
    is_local = (local_e >= 0) & (local_e < el)
    le = jnp.where(is_local, local_e, el)                              # el = drop bucket

    order, sle = _sort_by_expert(le, el)
    counts = jnp.bincount(sle, length=el + 1)
    offsets = jnp.cumsum(counts) - counts
    pos = jnp.arange(tl * k) - offsets[sle]
    keep = (sle < el) & (pos < capacity)
    dst = jnp.where(keep, sle * capacity + pos, el * capacity)         # OOB = drop

    rows = x[flat_t[order]].astype(cd)                                 # [Tl*k, d]
    buf = jnp.zeros((el * capacity, d), cd).at[dst].set(rows, mode="drop")
    buf = buf.reshape(el, capacity, d)

    h = common.activation(jnp.einsum("ecd,edf->ecf", buf, common.cast(wg, cd)),
                          cfg.act)
    h = h * jnp.einsum("ecd,edf->ecf", buf, common.cast(wu, cd))
    y = jnp.einsum("ecf,efd->ecd", h, common.cast(wd, cd))
    y = y.reshape(el * capacity, d)

    back = y.at[dst].get(mode="fill", fill_value=0)                    # [Tl*k, d]
    w = jnp.where(keep, flat_w[order], 0.0).astype(jnp.float32)
    out = jnp.zeros((tl, d), jnp.float32).at[flat_t[order]].add(
        back.astype(jnp.float32) * w[:, None])
    out = jax.lax.psum(out, tp_axis)

    # load-balance auxiliary loss (Switch-style), global means via psum
    ohot = jax.nn.one_hot(topi, m.n_experts, dtype=jnp.float32).sum(axis=1)
    f_sum = ohot.sum(axis=0)
    p_sum = gates.sum(axis=0)
    n_tok = jnp.float32(tl)
    if dp_axes:
        f_sum = jax.lax.psum(f_sum, dp_axes)                           # [E]
        p_sum = jax.lax.psum(p_sum, dp_axes)
        n_tok = jax.lax.psum(n_tok, dp_axes)
    f = f_sum / (n_tok * k)
    pbar = p_sum / n_tok
    aux = m.n_experts * jnp.sum(f * pbar)
    aux = jax.lax.pmean(aux, tp_axis)  # identical on every shard
    return out.astype(x.dtype), aux


def _sort_by_expert(le, el: int):
    """Stable argsort of the local expert ids ``le`` (el = not held),
    and the sorted ids. A sort of the packed key le*n+slot: one
    single-operand int32 sort instead of the (keys, iota) variadic
    comparator sort argsort lowers to (~7x slower on XLA CPU; same
    packing trick as core/fmmu/batch._insert_blocks)."""
    nk = le.shape[0]
    if (el + 1) * nk < 2 ** 31:
        skey = jnp.sort(le.astype(jnp.int32) * nk
                        + jnp.arange(nk, dtype=jnp.int32))
        return jnp.mod(skey, nk), skey // nk
    order = jnp.argsort(le, stable=True)   # huge shards: packing overflows
    return order, le[order]


def _moe_dropless_local(x, router, wg, wu, wd, *, cfg, rt: Runtime,
                        tp_axis: str):
    """Per-shard dropless body (runs under shard_map): this shard's held
    experts' part of the routed output, f32 [Tl, d]; no aux loss."""
    m = cfg.moe
    tl, d = x.shape
    el, k, cd = wg.shape[0], m.top_k, rt.compute_dtype
    with jax.named_scope("moe_route"):
        logits = x.astype(jnp.float32) @ router                        # [Tl,E]
        topl, topi = jax.lax.top_k(logits, k)
        topv = jax.nn.softmax(topl, axis=-1)
        local = topi.reshape(-1) - (m.held_offset
                                    + jax.lax.axis_index(tp_axis) * el)
        le = jnp.where((local >= 0) & (local < el), local, el)
        order, sle = _sort_by_expert(le, el)
        # a token's k experts are distinct, so at most min(k, el) of its
        # assignments are held here, and they sort first
        n_rows = tl * min(k, el)
        order, sle = order[:n_rows], sle[:n_rows]
        sizes = jnp.bincount(le, length=el + 1)[:el].astype(jnp.int32)
        tok = order // k
        rows = x[tok].astype(cd)
    with jax.named_scope("moe_experts"):
        gmm = functools.partial(ops.grouped_matmul, group_sizes=sizes,
                                impl=rt.kernel_impl)
        h = common.activation(gmm(rows, common.cast(wg, cd)), cfg.act) \
            * gmm(rows, common.cast(wu, cd))
        y = gmm(h, common.cast(wd, cd))
        keep = sle < el          # rows past the held groups are not written
        w = jnp.where(keep, topv.reshape(-1)[order], 0.0)
        y = jnp.where(keep[:, None], y.astype(jnp.float32), 0.0) * w[:, None]
        out = jnp.zeros((tl, d), jnp.float32).at[tok].add(y)
    return jax.lax.psum(out, tp_axis).astype(x.dtype), jnp.float32(0.0)


def apply_moe(params, x, cfg, rt: Runtime, ctx, *, dense_params=None,
              dropless: bool = False):
    """x [B,S,d] -> ([B,S,d], aux_loss scalar). ctx: ParallelCtx.
    ``dropless`` is the serving path (module docstring); otherwise the
    static-capacity training path."""
    from repro.models import mlp as mlp_mod
    m = cfg.moe
    b, s, d = x.shape
    # tiny batches (decode at global_batch < dp_size) replicate tokens
    # across the data axes instead of sharding them
    shard_tokens = (b % ctx.dp_size) == 0
    dp_axes = tuple(ctx.dp) if shard_tokens else ()
    tl = (b // ctx.dp_size if shard_tokens else b) * s
    if dropless:
        body = functools.partial(_moe_dropless_local, cfg=cfg, rt=rt,
                                 tp_axis=ctx.tp)
    else:
        cf = (rt.capacity_factor if rt.capacity_factor is not None
              else m.capacity_factor)
        capacity = _capacity(tl, m.n_experts, m.top_k, cf)
        body = functools.partial(_moe_local, cfg=cfg, rt=rt, tp_axis=ctx.tp,
                                 dp_axes=dp_axes, capacity=capacity)
    if shard_tokens:
        dp_spec = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    else:
        dp_spec = None
    fn = shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dp_spec, None), P(None, None),
                  P(ctx.tp, None, None), P(ctx.tp, None, None),
                  P(ctx.tp, None, None)),
        out_specs=(P(dp_spec, None), P()),
        check_vma=False)
    x2 = x.reshape(b * s, d)
    out, aux = fn(x2, params["router"], params["wg"], params["wu"],
                  params["wd"])
    out = out.reshape(b, s, d)
    if "shared" in params:
        with jax.named_scope("shared_expert"):
            out = out + mlp_mod.apply_mlp(params["shared"], x, cfg, rt)
    if dense_params is not None:  # arctic: parallel dense residual MLP
        out = out + mlp_mod.apply_mlp(dense_params, x, cfg, rt)
    return out, aux * m.router_aux_weight


def apply_moe_dense_ref(params, x, cfg, rt: Runtime):
    """O(T*E) dense reference (tests): every held expert runs every
    token; the shared expert is added once."""
    from repro.models import mlp as mlp_mod
    m = cfg.moe
    cd = rt.compute_dtype
    b, s, d = x.shape
    x2 = x.reshape(-1, d)
    gates = jax.nn.softmax(x2.astype(jnp.float32) @ params["router"], axis=-1)
    topv, topi = jax.lax.top_k(gates, m.top_k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    full = jnp.zeros_like(gates).at[jnp.arange(x2.shape[0])[:, None], topi].set(topv)
    full = full[:, m.held_offset:m.held_offset + m.held]
    h = common.activation(jnp.einsum("td,edf->tef", x2.astype(cd),
                                     common.cast(params["wg"], cd)), cfg.act)
    h = h * jnp.einsum("td,edf->tef", x2.astype(cd), common.cast(params["wu"], cd))
    y = jnp.einsum("tef,efd->ted", h, common.cast(params["wd"], cd))
    out = jnp.einsum("ted,te->td", y.astype(jnp.float32), full)
    out = out.reshape(b, s, d).astype(x.dtype)
    if "shared" in params:
        out = out + mlp_mod.apply_mlp(params["shared"], x, cfg, rt)
    return out
