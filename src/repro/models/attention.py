"""GQA attention: full / sliding-window / softcapped; train, prefill,
paged decode, and cross-attention paths.

Projections are kept 3D ([d, H, hd]) so head sharding is a single spec
axis; parallel/sharding.py replicates the head axis when it does not
divide the model-axis size (e.g. arctic's 56 Q heads, every kv=8 arch).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.parallel.sharding import shard_map
from repro.models import common
from repro.models.common import Runtime, apply_rope, rope_angles


def init_attention(key, cfg, dtype, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    params = {
        "wq": common.init_dense(ks[0], d, h * hd, dtype).reshape(d, h, hd),
        "wk": common.init_dense(ks[1], d, kv * hd, dtype).reshape(d, kv, hd),
        "wv": common.init_dense(ks[2], d, kv * hd, dtype).reshape(d, kv, hd),
        "wo": common.init_dense(ks[3], h * hd, d, dtype).reshape(h, hd, d),
    }
    if cfg.qkv_bias and not cross:
        params["bq"] = jnp.zeros((h, hd), dtype)
        params["bk"] = jnp.zeros((kv, hd), dtype)
        params["bv"] = jnp.zeros((kv, hd), dtype)
    return params


def attention_specs(cfg, *, cross: bool = False):
    specs = {
        "wq": P(None, "model", None),
        "wk": P(None, "model", None),
        "wv": P(None, "model", None),
        "wo": P("model", None, None),
    }
    if cfg.qkv_bias and not cross:
        specs["bq"] = P("model", None)
        specs["bk"] = P("model", None)
        specs["bv"] = P("model", None)
    return specs


def _masked_impl(rt: Runtime) -> str:
    """Kernel lowering for calls that carry segment or page masks: the
    Pallas kernels take none (ops raises), so these name the blocked
    lowering, or the naive oracle when the runtime asks for it."""
    return "naive" if rt.kernel_impl == "naive" else "blocked"


# ----------------------------------------------------------------------
def _project_qkv(params, x, cfg, rt, positions, *, rope: bool = True):
    """x [B,S,d] -> q [B,S,H,hd], k,v [B,S,KV,hd] (compute dtype)."""
    cd = rt.compute_dtype
    xq = jnp.einsum("bsd,dhk->bshk", x, common.cast(params["wq"], cd))
    xk = jnp.einsum("bsd,dhk->bshk", x, common.cast(params["wk"], cd))
    xv = jnp.einsum("bsd,dhk->bshk", x, common.cast(params["wv"], cd))
    if "bq" in params:
        xq = xq + common.cast(params["bq"], cd)
        xk = xk + common.cast(params["bk"], cd)
        xv = xv + common.cast(params["bv"], cd)
    if rope and cfg.use_rope:
        cos, sin = rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        xq = apply_rope(xq, cos, sin)
        xk = apply_rope(xk, cos, sin)
    return xq, xk, xv


def attn_forward(params, x, cfg, rt: Runtime, *, positions, kind="global",
                 segment_ids=None, bidirectional=False,
                 return_kv=False):
    """Training / prefill self-attention. x [B,S,d] -> [B,S,d]."""
    q, k, v = _project_qkv(params, x, cfg, rt, positions)
    window = cfg.sliding_window if kind == "local" else 0
    segs = (segment_ids, segment_ids) if segment_ids is not None else None
    out = ops.flash_attention(
        q, k, v, causal=not bidirectional, window=window,
        softcap=cfg.attn_softcap, segment_ids=segs,
        bidirectional=bidirectional,
        impl=rt.kernel_impl if segs is None else _masked_impl(rt),
        q_chunk=rt.q_chunk, kv_chunk=rt.kv_chunk)
    y = jnp.einsum("bshk,hkd->bsd", out, common.cast(params["wo"], rt.compute_dtype))
    if return_kv:
        return y, (k, v)
    return y


def cross_forward(params, x, kv_cache, cfg, rt: Runtime, *, src_valid=None):
    """Decoder cross-attention. kv_cache = (k,v) [B,Ssrc,KV,hd]."""
    cd = rt.compute_dtype
    q = jnp.einsum("bsd,dhk->bshk", x, common.cast(params["wq"], cd))
    k, v = kv_cache
    segs = None
    if src_valid is not None:
        # mask invalid source positions via segment ids (1=valid, 0=pad)
        seg_q = jnp.ones(q.shape[:2], jnp.int32)
        segs = (seg_q, src_valid.astype(jnp.int32))
    out = ops.flash_attention(q, k, v, causal=False, bidirectional=True,
                              segment_ids=segs,
                              impl=(rt.kernel_impl if segs is None
                                    else _masked_impl(rt)),
                              q_chunk=rt.q_chunk, kv_chunk=rt.kv_chunk)
    return jnp.einsum("bshk,hkd->bsd", out, common.cast(params["wo"], cd))


def cross_kv(params, enc_out, cfg, rt: Runtime):
    """Precompute cross-attention K/V from encoder output (once)."""
    cd = rt.compute_dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, common.cast(params["wk"], cd))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, common.cast(params["wv"], cd))
    return k, v


# ----------------------------------------------------------------------
# Paged decode
# ----------------------------------------------------------------------
def write_kv_page(pool_k, pool_v, k_new, v_new, block_table, ctx_lens,
                  page_size: int):
    """Scatter one new token's K/V into the paged pool.
    pools [NB,P,KV*hd]; k_new/v_new [B,KV,hd]; returns updated pools."""
    b = k_new.shape[0]
    k_new = k_new.reshape(b, -1)
    v_new = v_new.reshape(b, -1)
    logical = ctx_lens // page_size
    offs = ctx_lens % page_size
    pages = block_table[jnp.arange(b), logical]
    pool_k = pool_k.at[pages, offs].set(k_new.astype(pool_k.dtype))
    pool_v = pool_v.at[pages, offs].set(v_new.astype(pool_v.dtype))
    return pool_k, pool_v


def attn_decode_paged(params, x, cfg, rt: Runtime, *, pool_k, pool_v,
                      block_table, ctx_lens, kind="global",
                      return_stats=False):
    """One-token decode. x [B,d]; pools [NB,P,KV*hd]; returns
    (y [B,d], pool_k, pool_v) (+ (m,l) stats for cross-shard combine)."""
    positions = ctx_lens[:, None]                      # [B,1]
    q, k, v = _project_qkv(params, x[:, None, :], cfg, rt, positions)
    pool_k, pool_v = write_kv_page(pool_k, pool_v, k[:, 0], v[:, 0],
                                   block_table, ctx_lens, rt.page_size)
    window = cfg.sliding_window if kind == "local" else 0
    res = ops.paged_attention(
        q[:, 0], pool_k, pool_v, block_table, ctx_lens + 1,
        softcap=cfg.attn_softcap, window=window,
        return_stats=return_stats, impl=rt.kernel_impl,
        pages_per_chunk=rt.paged_chunk)
    if return_stats:
        out, (m, l) = res
    else:
        out = res
    y = jnp.einsum("bhk,hkd->bd", out, common.cast(params["wo"], rt.compute_dtype))
    if return_stats:
        return y, pool_k, pool_v, (m, l)
    return y, pool_k, pool_v


def attn_decode_paged_striped(params, x, cfg, rt: Runtime, ctx, *,
                              pool_k, pool_v, block_table, ctx_lens,
                              kind="global"):
    """Page-striped decode (the flash-channel analogy, DESIGN.md §2):
    pool blocks are range-partitioned across the combine axes; each shard
    attends only its owned pages (page_mask) and partial softmax results
    merge with the flash-decoding combine — the cross-shard traffic drops
    from per-position logits/values to one (o, m, l) triple per layer.

    combine axes: ('model',) when the batch shards over data (each data
    shard holds its own sequences' pages); ('data','model') for
    batch < dp_size (one giant context striped over every chip)."""
    import functools
    from repro.kernels.ref import combine_partial_attention

    b = x.shape[0]
    batch_sharded = (b % ctx.dp_size) == 0 and b >= ctx.dp_size
    # pools are range-partitioned over (data, model) always; the batch
    # -sharded case relies on the allocator placing a sequence's blocks
    # inside its data shard's range, so the softmax combine only needs to
    # cross 'model'. batch < dp replicates q and combines everywhere.
    own_axes = tuple(ctx.dp) + ("model",)
    combine_axes = ("model",) if batch_sharded else own_axes
    positions = ctx_lens[:, None]
    q, k, v = _project_qkv(params, x[:, None, :], cfg, rt, positions)
    window = cfg.sliding_window if kind == "local" else 0

    mesh = ctx.mesh

    def body(qb, kn, vn, pk, pv, table, ctxl):
        rows_local = pk.shape[0]
        lid = jnp.int32(0)
        for ax in own_axes:
            lid = lid * mesh.shape[ax] + jax.lax.axis_index(ax)
        lo = lid * rows_local
        owned = (table >= lo) & (table < lo + rows_local)
        local_table = jnp.where(owned, table - lo, 0)
        bb = qb.shape[0]
        logical = ctxl // rt.page_size
        offs = jnp.mod(ctxl, rt.page_size)
        tgt = table[jnp.arange(bb), logical]
        t_owned = (tgt >= lo) & (tgt < lo + rows_local)
        # scatter-add of (new - current), masked to owned targets: exact
        # set() for the owning shard, a literal +0 elsewhere — immune to
        # index collisions and to any OOB-mode lowering surprises.
        rows = jnp.where(t_owned, tgt - lo, 0)
        own2 = t_owned[:, None]
        cur_k = pk[rows, offs]
        cur_v = pv[rows, offs]
        pk = pk.at[rows, offs].add(
            jnp.where(own2, kn.reshape(bb, -1).astype(pk.dtype) - cur_k, 0))
        pv = pv.at[rows, offs].add(
            jnp.where(own2, vn.reshape(bb, -1).astype(pv.dtype) - cur_v, 0))
        o, (m, l) = ops.paged_attention(
            qb, pk, pv, local_table, ctxl + 1, softcap=cfg.attn_softcap,
            window=window, page_mask=owned, return_stats=True,
            impl=_masked_impl(rt), pages_per_chunk=rt.paged_chunk)
        outs = jax.lax.all_gather(o.astype(jnp.float32), combine_axes)
        ms = jax.lax.all_gather(m, combine_axes)
        ls = jax.lax.all_gather(l, combine_axes)
        return combine_partial_attention(outs, ms, ls).astype(qb.dtype), \
            pk, pv

    dspec = "data" if batch_sharded else None
    pool_spec = P(own_axes if len(own_axes) > 1 else own_axes[0],
                  None, None)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(P(dspec, None, None), P(dspec, None, None),
                  P(dspec, None, None), pool_spec, pool_spec,
                  P(dspec, None), P(dspec)),
        out_specs=(P(dspec, None, None), pool_spec, pool_spec),
        check_vma=False)
    y, pool_k, pool_v = fn(q[:, 0], k[:, 0], v[:, 0], pool_k, pool_v,
                           block_table, ctx_lens)
    y = jnp.einsum("bhk,hkd->bd", y.astype(rt.compute_dtype),
                   common.cast(params["wo"], rt.compute_dtype))
    return y, pool_k, pool_v


def attn_decode_dense(params, x, cfg, rt: Runtime, *, cache_k, cache_v,
                      ctx_lens):
    """One-token decode against a dense (non-paged) KV cache
    [B,Smax,KV,hd] — the non-FMMU baseline path."""
    b, smax = cache_k.shape[0], cache_k.shape[1]
    positions = ctx_lens[:, None]
    q, k, v = _project_qkv(params, x[:, None, :], cfg, rt, positions)
    cache_k = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n.astype(c.dtype), i, 0))(cache_k, k, ctx_lens)
    cache_v = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n.astype(c.dtype), i, 0))(cache_v, v, ctx_lens)
    kf = cache_k.astype(jnp.float32)
    vf = cache_v.astype(jnp.float32)
    h = q.shape[2]
    kv = kf.shape[2]
    qg = q[:, 0].astype(jnp.float32).reshape(b, kv, h // kv, -1)
    qg = qg * (1.0 / jnp.sqrt(jnp.float32(q.shape[-1])))
    s = jnp.einsum("bkgd,bskd->bkgs", qg, kf)
    s = common.softcap(s, cfg.attn_softcap)
    valid = jnp.arange(smax)[None, :] <= ctx_lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vf).reshape(b, h, -1)
    y = jnp.einsum("bhk,hkd->bd", out.astype(rt.compute_dtype),
                   common.cast(params["wo"], rt.compute_dtype))
    return y, cache_k, cache_v
