"""Decoder/encoder stacks: heterogeneous repeating super-blocks
(jamba's 1:7 attn:mamba + alternating MoE, gemma2's local/global pairs)
scanned with ``lax.scan`` over periods and rematerialized per policy.

Layer kinds are static per intra-period index j (cfg.period is the lcm
of all layer patterns), so one traced period body serves every period.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import attention, common, mlp, moe, ssm
from repro.models.common import Runtime


# ----------------------------------------------------------------------
# init / specs
# ----------------------------------------------------------------------
def _init_layer(key, cfg, j: int, dtype, *, cross: bool):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"ln1": common.init_rms_norm(cfg.d_model, dtype)}
    if cfg.layer_kind(j) == "attn":
        p["mixer"] = attention.init_attention(ks[0], cfg, dtype)
    else:
        p["mixer"] = ssm.init_ssm(ks[0], cfg, dtype)
    if cfg.post_norms:
        p["post1"] = common.init_rms_norm(cfg.d_model, dtype)
    if cross:
        p["cross_ln"] = common.init_rms_norm(cfg.d_model, dtype)
        p["cross"] = attention.init_attention(ks[1], cfg, dtype, cross=True)
    ffn: Dict[str, Any] = {}
    if cfg.is_moe_layer(j):
        ffn["moe"] = moe.init_moe(ks[2], cfg, dtype)
        if cfg.moe.dense_residual:
            ffn["dense"] = mlp.init_mlp(ks[3], cfg, dtype)
    elif cfg.d_ff:
        ffn["dense"] = mlp.init_mlp(ks[2], cfg, dtype)
    if ffn:
        p["ln2"] = common.init_rms_norm(cfg.d_model, dtype)
        p["ffn"] = ffn
        if cfg.post_norms:
            p["post2"] = common.init_rms_norm(cfg.d_model, dtype)
    return p


def _layer_specs(cfg, j: int, *, cross: bool):
    s: Dict[str, Any] = {"ln1": P(None,)}
    if cfg.layer_kind(j) == "attn":
        s["mixer"] = attention.attention_specs(cfg)
    else:
        s["mixer"] = ssm.ssm_specs(cfg)
    if cfg.post_norms:
        s["post1"] = P(None,)
    if cross:
        s["cross_ln"] = P(None,)
        s["cross"] = attention.attention_specs(cfg, cross=True)
    ffn: Dict[str, Any] = {}
    if cfg.is_moe_layer(j):
        ffn["moe"] = moe.moe_specs(cfg)
        if cfg.moe.dense_residual:
            ffn["dense"] = mlp.mlp_specs(cfg)
    elif cfg.d_ff:
        ffn["dense"] = mlp.mlp_specs(cfg)
    if ffn:
        s["ln2"] = P(None,)
        s["ffn"] = ffn
        if cfg.post_norms:
            s["post2"] = P(None,)
    return s


def init_stack(key, cfg, dtype, *, cross: bool = False):
    """Stacked params: every leaf gains a leading [n_periods] axis."""
    period = cfg.period
    n_periods = cfg.n_layers // period
    periods = []
    for pidx in range(n_periods):
        kp = jax.random.fold_in(key, pidx)
        periods.append([
            _init_layer(jax.random.fold_in(kp, j), cfg, j, dtype, cross=cross)
            for j in range(period)])
    return common.tree_stack(periods)


def stack_specs(cfg, *, cross: bool = False):
    period_specs = [_layer_specs(cfg, j, cross=cross)
                    for j in range(cfg.period)]
    return common.stacked_specs(period_specs)


def _residual(x, y, cfg):
    """x plus the branch y, scaled by granite's residual_multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


# ----------------------------------------------------------------------
# forward (train / prefill)
# ----------------------------------------------------------------------
def _apply_layer_full(lp, x, cfg, rt: Runtime, ctx, j: int, *, positions,
                      segment_ids, bidirectional, enc_out, src_valid,
                      collect, dropless):
    """One layer, full-sequence. Returns (x, aux, collected)."""
    aux = jnp.float32(0.0)
    col: Dict[str, Any] = {}
    h = common.rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.layer_kind(j) == "attn":
        if collect:
            y, (k, v) = attention.attn_forward(
                lp["mixer"], h, cfg, rt, positions=positions,
                kind=cfg.attn_kind(j), segment_ids=segment_ids,
                bidirectional=bidirectional, return_kv=True)
            col["kv"] = (k, v)
        else:
            y = attention.attn_forward(
                lp["mixer"], h, cfg, rt, positions=positions,
                kind=cfg.attn_kind(j), segment_ids=segment_ids,
                bidirectional=bidirectional)
    else:
        if collect:
            y, state = ssm.ssm_forward(lp["mixer"], h, cfg, rt,
                                       return_state=True)
            col["ssm"] = state
        else:
            y = ssm.ssm_forward(lp["mixer"], h, cfg, rt)
    if cfg.post_norms:
        y = common.rms_norm(y, lp["post1"], cfg.norm_eps)
    x = _residual(x, y, cfg)
    if enc_out is not None and "cross" in lp:
        h = common.rms_norm(x, lp["cross_ln"], cfg.norm_eps)
        kv = attention.cross_kv(lp["cross"], enc_out, cfg, rt)
        y = attention.cross_forward(lp["cross"], h, kv, cfg, rt,
                                    src_valid=src_valid)
        x = x + y
        if collect:
            col["cross_kv"] = kv
    if "ffn" in lp:
        h = common.rms_norm(x, lp["ln2"], cfg.norm_eps)
        if "moe" in lp["ffn"]:
            y, aux = moe.apply_moe(
                lp["ffn"]["moe"], h, cfg, rt, ctx,
                dense_params=lp["ffn"].get("dense"), dropless=dropless)
        else:
            y = mlp.apply_mlp(lp["ffn"]["dense"], h, cfg, rt)
        if cfg.post_norms:
            y = common.rms_norm(y, lp["post2"], cfg.norm_eps)
        x = _residual(x, y, cfg)
    return x, aux, col


def stack_forward(params, x, cfg, rt: Runtime, ctx, *, positions,
                  segment_ids=None, bidirectional=False, enc_out=None,
                  src_valid=None, collect_caches=False, dropless=False):
    """Full stack. Returns (x, aux_total, caches or None).

    caches (when collect_caches): pytree of per-period stacked collections
    — leaves [n_periods, ...] with a per-period list over attn/ssm layers.
    ``dropless``: the MoE layers' serving path (models/moe.py).
    """
    period = cfg.period

    def body(carry, pp):
        xc, auxc = carry
        cols = []
        for j in range(period):
            xc, aux_j, col = _apply_layer_full(
                pp[j], xc, cfg, rt, ctx, j,
                positions=positions, segment_ids=segment_ids,
                bidirectional=bidirectional, enc_out=enc_out,
                src_valid=src_valid, collect=collect_caches,
                dropless=dropless)
            auxc = auxc + aux_j
            cols.append(col)
        return (xc, auxc), cols

    if rt.remat != "none":
        body = jax.checkpoint(body, policy=common.remat_policy(rt.remat),
                              prevent_cse=False)
    aux0 = jnp.float32(0.0)
    if rt.scan_layers:
        (x, aux), cols = jax.lax.scan(body, (x, aux0), params)
    else:
        n_periods = cfg.n_layers // period
        all_cols = []
        for pidx in range(n_periods):
            pp = jax.tree.map(lambda t: t[pidx], params)
            (x, aux0), cols = body((x, aux0), pp)
            all_cols.append(cols)
        aux = aux0
        cols = common.tree_stack(all_cols) if collect_caches else None
    return x, aux, (cols if collect_caches else None)


# ----------------------------------------------------------------------
# decode (one token, paged KV + recurrent states)
# ----------------------------------------------------------------------
def init_decode_caches(cfg, rt: Runtime, batch: int, max_pages_per_seq: int,
                       n_blocks: int, dtype, *, src_len: int = 0):
    """Allocate paged KV pools / SSM states, stacked [n_periods, L_kind, ...].
    KV pools are lane-dense [.., n_blocks, page, KV*hd]
    (kernels/paged_attention.py)."""
    period = cfg.period
    n_periods = cfg.n_layers // period
    attn_js = [j for j in range(period) if cfg.layer_kind(j) == "attn"]
    ssm_js = [j for j in range(period) if cfg.layer_kind(j) == "mamba"]
    caches: Dict[str, Any] = {}
    if attn_js:
        shape = (n_periods, len(attn_js), n_blocks, rt.page_size,
                 cfg.n_kv_heads * cfg.head_dim)
        caches["pool_k"] = jnp.zeros(shape, dtype)
        caches["pool_v"] = jnp.zeros(shape, dtype)
    if ssm_js:
        s = cfg.ssm
        di = s.d_inner(cfg.d_model)
        nh = s.n_heads(cfg.d_model)
        caches["conv"] = jnp.zeros(
            (n_periods, len(ssm_js), batch, s.conv_dim - 1,
             di + 2 * s.d_state), dtype)
        caches["ssm"] = jnp.zeros(
            (n_periods, len(ssm_js), batch, nh, s.head_dim, s.d_state),
            jnp.float32)
    if cfg.n_enc_layers and src_len:
        caches["cross_k"] = jnp.zeros(
            (n_periods, period, batch, src_len, cfg.n_kv_heads, cfg.head_dim),
            dtype)
        caches["cross_v"] = jnp.zeros_like(caches["cross_k"])
    return caches


def stack_decode(params, x, caches, cfg, rt: Runtime, ctx, *, ctx_lens,
                 block_table, src_valid=None):
    """One decode step through the stack.
    x [B,d]; caches from init_decode_caches (pools already filled by
    prefill); block_table [B, MAXP] shared across layers.

    The KV pools ride in the layer scan's carry, seen as
    [n_periods * n_attn, NB, P, KV*hd] (merging the two leading axes is
    a bitcast): each attention layer scatters its token's rows into the
    carried stack in place and paged attention reads the stack through
    the layer's index, so no layer's pool is sliced out or written back
    whole, and a scan of decode steps around this one keeps the pool in
    one buffer. The Mamba layers' conv and SSM states ride the carry
    the same way, [n_periods * n_mamba, B, ...], and the state kernel
    updates its layer in place. Cross-attention caches, which decode
    only reads, stay in the scan's xs / ys. MoE layers take the
    dropless serving path."""
    period = cfg.period
    n_periods = cfg.n_layers // period
    attn_js = [j for j in range(period) if cfg.layer_kind(j) == "attn"]
    ssm_js = [j for j in range(period) if cfg.layer_kind(j) == "mamba"]
    a_of = {j: i for i, j in enumerate(attn_js)}
    s_of = {j: i for i, j in enumerate(ssm_js)}
    stacks = {k: caches[k].reshape(-1, *caches[k].shape[2:])
             for k in ("pool_k", "pool_v", "conv", "ssm") if k in caches}
    small = {k: v for k, v in caches.items() if k not in stacks}

    def body(carry, scanned):
        xc, stacks = carry
        pidx, pp, cc = scanned
        stacks, new_cc = dict(stacks), dict(cc)
        for j in range(period):
            lp = pp[j]
            h = common.rms_norm(xc, lp["ln1"], cfg.norm_eps)
            if cfg.layer_kind(j) == "attn":
                li = pidx * len(attn_js) + a_of[j]
                if rt.shard_kv_pool_pages:
                    # the striped kernel runs under shard_map on one
                    # layer's pool: slice it and write it back
                    with jax.named_scope("kv_pool"):
                        pool_k = stacks["pool_k"][li]
                        pool_v = stacks["pool_v"][li]
                    y, pk, pv = attention.attn_decode_paged_striped(
                        lp["mixer"], h, cfg, rt, ctx,
                        pool_k=pool_k, pool_v=pool_v,
                        block_table=block_table, ctx_lens=ctx_lens,
                        kind=cfg.attn_kind(j))
                    with jax.named_scope("kv_pool"):
                        stacks["pool_k"] = stacks["pool_k"].at[li].set(pk)
                        stacks["pool_v"] = stacks["pool_v"].at[li].set(pv)
                else:
                    y, stacks["pool_k"], stacks["pool_v"] = \
                        attention.attn_decode_paged(
                            lp["mixer"], h, cfg, rt,
                            pool_k=stacks["pool_k"], pool_v=stacks["pool_v"],
                            block_table=block_table, ctx_lens=ctx_lens,
                            kind=cfg.attn_kind(j), layer=li)
            else:
                y, (stacks["conv"], stacks["ssm"]) = ssm.ssm_decode(
                    lp["mixer"], h, (stacks["conv"], stacks["ssm"]), cfg, rt,
                    layer=pidx * len(ssm_js) + s_of[j])
            if cfg.post_norms:
                y = common.rms_norm(y, lp["post1"], cfg.norm_eps)
            xc = _residual(xc, y, cfg)
            if "cross" in lp:
                h = common.rms_norm(xc, lp["cross_ln"], cfg.norm_eps)
                y3 = attention.cross_forward(
                    lp["cross"], h[:, None, :],
                    (cc["cross_k"][j], cc["cross_v"][j]), cfg, rt,
                    src_valid=src_valid)
                xc = xc + y3[:, 0]
            if "ffn" in lp:
                h = common.rms_norm(xc, lp["ln2"], cfg.norm_eps)
                if "moe" in lp["ffn"]:
                    y2, _ = moe.apply_moe(lp["ffn"]["moe"], h[:, None, :],
                                          cfg, rt, ctx,
                                          dense_params=lp["ffn"].get("dense"),
                                          dropless=True)
                    y2 = y2[:, 0]
                else:
                    y2 = mlp.apply_mlp(lp["ffn"]["dense"], h[:, None, :],
                                       cfg, rt)[:, 0]
                if cfg.post_norms:
                    y2 = common.rms_norm(y2, lp["post2"], cfg.norm_eps)
                xc = _residual(xc, y2, cfg)
        return (xc, stacks), new_cc

    if rt.scan_layers:
        (x, stacks), small = jax.lax.scan(
            body, (x, stacks),
            (jnp.arange(n_periods, dtype=jnp.int32), params, small))
    else:
        outs = []
        for pidx in range(n_periods):
            pp = jax.tree.map(lambda t: t[pidx], params)
            cc = jax.tree.map(lambda t: t[pidx], small)
            (x, stacks), ncc = body((x, stacks), (pidx, pp, cc))
            outs.append(ncc)
        small = common.tree_stack(outs)
    return x, {**small,
               **{k: v.reshape(caches[k].shape) for k, v in stacks.items()}}
