"""Pallas TPU paged decode attention over FMMU block tables.

The block table (the FMMU's translation output: logical page -> physical
block) rides in as a *scalar-prefetch* operand, so each grid step's KV
tile is DMA'd straight from the physical block the table names —
`k_pool[table[b, i]]` is expressed in the BlockSpec index_map and the
Mosaic pipeline overlaps tile i+1's DMA with tile i's compute. This is
the TPU rendering of the paper's "FMMU keeps all flash channels busy":
the map unit's output drives the memory pipeline directly.

Pools are lane-dense, [NB, P, KV*D]: the TPU tiles the last two dims of
an array (8 sublanes x 128 lanes), so a [.., KV, D] pool with D = 64
would be padded 2x in lanes (4x in bf16, where sublanes pack in
pairs) and XLA would copy the whole pool around every kernel call. One
page of all KV heads is then a [P, KV*D] tile, and q arrives
block-diagonal — row h holds its head's query in the columns of kv
head h // group and zeros elsewhere — so one [H, KV*D] x [KV*D, P]
matmul gives every head's scores without an in-kernel reshape; the
[H, KV*D] output is cut back to each head's own kv columns outside.

Grid = (batch, n_pages); online-softmax stats carried in VMEM scratch
across the page axis; per-sequence length masking from a prefetched
ctx_lens vector. Returns optional (m, l) stats for the cross-shard
flash-decoding combine used by sequence-parallel 500k decode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _pa_kernel(table_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
               l_ref, macc, lacc, acc, *, scale, softcap, window, page):
    b = pl.program_id(0)
    i = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        macc[...] = jnp.full_like(macc, NEG_INF)
        lacc[...] = jnp.zeros_like(lacc)
        acc[...] = jnp.zeros_like(acc)

    ctx = ctx_ref[b]
    # page i covers positions [i*page, (i+1)*page)
    live = i * page < ctx
    if window and window > 0:   # pages wholly below the window: skip DMA'd tile
        live &= (i + 1) * page > ctx - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale        # [H, KV*D]
        k = k_ref[0].astype(jnp.float32)                # [page, KV*D]
        v = v_ref[0].astype(jnp.float32)
        h = q.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        # s: [H, page]
        if softcap and softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        pos = i * page + jax.lax.broadcasted_iota(jnp.int32, (h, page), 1)
        valid = pos < ctx
        if window and window > 0:
            valid &= pos >= ctx - window
        s = jnp.where(valid, s, NEG_INF)
        m_prev = macc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # [H, page]
        lacc[...] = lacc[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = jax.lax.dot(p, v, preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv                # [H, KV*D]
        macc[...] = m_new

    @pl.when(i == np_ - 1)
    def _finish():
        o_ref[0] = (acc[...] / jnp.maximum(lacc[...], 1e-30)).astype(o_ref.dtype)
        m_ref[0] = macc[...]
        l_ref[0] = lacc[...]


def paged_attention(q, k_pool, v_pool, block_table, ctx_lens, *,
                    softcap=0.0, window=0, return_stats=False,
                    interpret=False):
    """q [B,H,D]; pools [NB,P,KV*D]; block_table [B,MAXP] int32;
    ctx_lens [B] int32 -> [B,H,D] (+ (m,l) [B,H] fp32)."""
    b, h, d = q.shape
    nb, page, kvd = k_pool.shape
    kv = kvd // d
    maxp = block_table.shape[1]
    head_kv = jnp.arange(h) // (h // kv)
    own = head_kv[:, None] == jnp.arange(kv)[None, :]      # [H, KV]
    q_bd = jnp.where(own[None, :, :, None], q[:, :, None, :],
                     0).reshape(b, h, kvd)
    kernel = functools.partial(
        _pa_kernel, scale=1.0 / math.sqrt(d), softcap=softcap,
        window=window, page=page)
    out, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, maxp),
            in_specs=[
                pl.BlockSpec((1, h, kvd), lambda bi, i, tbl, ctx: (bi, 0, 0)),
                pl.BlockSpec((1, page, kvd),
                             lambda bi, i, tbl, ctx: (tbl[bi, i], 0, 0)),
                pl.BlockSpec((1, page, kvd),
                             lambda bi, i, tbl, ctx: (tbl[bi, i], 0, 0)),
            ],
            # stats are [B, H, 1]: the last two block dims must be
            # (8, 128)-aligned or span the array, which (1, H) is not
            out_specs=[
                pl.BlockSpec((1, h, kvd), lambda bi, i, tbl, ctx: (bi, 0, 0)),
                pl.BlockSpec((1, h, 1), lambda bi, i, tbl, ctx: (bi, 0, 0)),
                pl.BlockSpec((1, h, 1), lambda bi, i, tbl, ctx: (bi, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, kvd), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, h, kvd), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_table, ctx_lens, q_bd, k_pool, v_pool)
    out = out.reshape(b, h, kv, d)[:, jnp.arange(h), head_kv]   # [B, H, D]
    if return_stats:
        return out, (m[..., 0], l[..., 0])
    return out
