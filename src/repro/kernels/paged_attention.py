"""Pallas TPU paged decode attention over FMMU block tables.

The block table (the FMMU's translation output: logical page -> physical
block) rides in as a *scalar-prefetch* operand, and the kernel copies
each page straight from the physical block the table names: one async
copy per page, ``k_pool[layer, table[b, j]]`` into a VMEM block buffer.
This is the TPU rendering of the paper's "FMMU keeps all flash channels
busy": the map unit's output drives the memory pipeline directly.

Pools are lane-dense, [NB, P, KV*D]: the TPU tiles the last two dims of
an array (8 sublanes x 128 lanes), so a [.., KV, D] pool with D = 64
would be padded 2x in lanes (4x in bf16, where sublanes pack in
pairs) and XLA would copy the whole pool around every kernel call. One
page of all KV heads is then a [P, KV*D] tile, and q arrives
block-diagonal — row h holds its head's query in the columns of kv
head h // group and zeros elsewhere — so one [H, KV*D] x [KV*D, T]
matmul gives every head's scores without an in-kernel reshape; the
[H, KV*D] output is cut back to each head's own kv columns outside.

The pool may also be the whole stack's, [L, NB, P, KV*D], with the
layer to read as a third scalar-prefetch operand: the decode scan
carries every layer's pool and hands the kernel the stack, which stays
in HBM (``memory_space=pl.ANY``), so no layer's pool is ever sliced out
(copied) for the call. A single-layer pool is the stack of one.

Grid = (batch,). The unit of work is a block of ``ppb`` pages: a lane
loops over its live blocks only — pages j < ceil(ctx / P), and with a
sliding window only pages that reach into it — so a dead page costs
neither a copy nor compute, and a dead lane (ctx 0) copies nothing. The
block buffers are double-buffered: block n+1's copies (the next lane's
first block, after a lane's last) start before block n is computed.
Positions at or past ctx inside a partly live block hold whatever the
buffer held before (or nothing ever copied); their scores and their V
rows are both masked, so they contribute exactly zero even as NaN.

``ppb`` follows from the page tile's bytes (``pages_per_block``): a
fixed number of bytes per block, so pools with small pages (GLM's 8 KiB
bf16 tiles) take more pages a block than pools with large ones
(Mistral's 32 KiB). Online-softmax stats are carried in VMEM scratch
across a lane's blocks. Returns optional (m, l) stats for the
cross-shard flash-decoding combine used by sequence-parallel 500k
decode.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# bytes of one of K or V a block copies; the block is capped by the bucket
BLOCK_BYTES = 512 << 10


def pages_per_block(page, kvd, itemsize, max_pages):
    """Pages a block copies: BLOCK_BYTES over one page tile's bytes,
    at least 1 and at most the table's page bucket."""
    return max(1, min(max_pages, BLOCK_BYTES // (page * kvd * itemsize)))


def _pa_kernel(table_ref, ctx_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
               m_ref, l_ref, kbuf, vbuf, sems, nxt, macc, lacc, acc, *,
               scale, softcap, window, page, ppb):
    b = pl.program_id(0)
    n_lanes = pl.num_programs(0)
    max_pages = table_ref.shape[1]
    layer = layer_ref[0]
    bk = ppb * page

    def live(lane):
        """[lo, hi): the pages of ``lane`` attention reads."""
        ctx = ctx_ref[lane]
        hi = jnp.minimum((ctx + page - 1) // page, max_pages)
        lo = 0
        if window and window > 0:
            lo = jnp.minimum(jnp.maximum(ctx - window, 0) // page, hi)
        return lo, hi

    def n_blocks(lane):
        lo, hi = live(lane)
        return (hi - lo + ppb - 1) // ppb

    def block_copies(lane, n, slot, start):
        """Start (or wait for) the K and V copies of block n of
        ``lane`` into buffer ``slot``: its live pages alone."""
        lo, hi = live(lane)
        first = lo + n * ppb

        def one(j, carry):
            blk = table_ref[lane, first + j]
            rows = pl.ds(pl.multiple_of(j * page, page), page)
            for pool, buf, sem in ((k_hbm, kbuf, sems.at[0, slot]),
                                   (v_hbm, vbuf, sems.at[1, slot])):
                cp = pltpu.make_async_copy(pool.at[layer, blk],
                                           buf.at[slot, rows], sem)
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        lax.fori_loop(0, jnp.minimum(hi - first, ppb), one, 0)

    @pl.when(b == 0)
    def _first_lane():
        nxt[0] = 0          # buffer of this lane's first block
        nxt[1] = 0          # 1: its copies were started by the lane before

    ctx = ctx_ref[b]
    lo, _ = live(b)
    nblk = n_blocks(b)
    slot0 = nxt[0]

    @pl.when((nblk > 0) & (nxt[1] == 0))
    def _start_first():
        block_copies(b, 0, slot0, True)

    next_lane = jnp.minimum(b + 1, n_lanes - 1)
    prefetch_next = (b + 1 < n_lanes) & (n_blocks(next_lane) > 0)
    macc[...] = jnp.full_like(macc, NEG_INF)
    lacc[...] = jnp.zeros_like(lacc)
    acc[...] = jnp.zeros_like(acc)
    q = q_ref[0].astype(jnp.float32) * scale            # [H, KV*D]
    h, kvd = q.shape

    def body(n, carry):
        slot = (slot0 + n) % 2

        @pl.when(n + 1 < nblk)
        def _next_block():
            block_copies(b, n + 1, 1 - slot, True)

        @pl.when((n + 1 == nblk) & prefetch_next)
        def _next_lane():
            block_copies(next_lane, 0, 1 - slot, True)

        block_copies(b, n, slot, False)
        base = (lo + n * ppb) * page
        k = kbuf[slot].astype(jnp.float32)              # [bk, KV*D]
        v = vbuf[slot].astype(jnp.float32)
        row = base + lax.broadcasted_iota(jnp.int32, (bk, kvd), 0)
        pos = base + lax.broadcasted_iota(jnp.int32, (h, bk), 1)
        valid_row, valid = row < ctx, pos < ctx
        if window and window > 0:
            valid_row &= row >= ctx - window
            valid &= pos >= ctx - window
        # rows never copied (or stale) may hold NaN: zero their V rows,
        # not just their scores, so p * v is exactly 0 there
        v = jnp.where(valid_row, v, 0.0)
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        # s: [H, bk]
        if softcap and softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid, s, NEG_INF)
        m_prev = macc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                          # [H, bk]
        lacc[...] = lacc[...] * alpha + p.sum(axis=1, keepdims=True)
        pv = lax.dot(p, v, preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv                # [H, KV*D]
        macc[...] = m_new
        return carry

    lax.fori_loop(0, nblk, body, 0)
    nxt[0] = (slot0 + nblk) % 2
    nxt[1] = ((nblk > 0) & prefetch_next).astype(jnp.int32)
    o_ref[0] = (acc[...] / jnp.maximum(lacc[...], 1e-30)).astype(o_ref.dtype)
    m_ref[0] = macc[...]
    l_ref[0] = lacc[...]


def paged_attention(q, k_pool, v_pool, block_table, ctx_lens, *,
                    layer=None, softcap=0.0, window=0, scale=None,
                    return_stats=False, interpret=False):
    """q [B,H,D]; pools [NB,P,KV*D], or [L,NB,P,KV*D] with ``layer`` the
    one to read; block_table [B,MAXP] int32; ctx_lens [B] int32;
    ``scale`` multiplies q.k (None: 1/sqrt(D)) -> [B,H,D] (+ (m,l)
    [B,H] fp32). ``interpret`` runs the TPU interpreter on the CPU,
    which checks every copy's bounds and fills VMEM with NaN."""
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    b, h, d = q.shape
    _, nb, page, kvd = k_pool.shape
    kv = kvd // d
    maxp = block_table.shape[1]
    ppb = pages_per_block(page, kvd, k_pool.dtype.itemsize, maxp)
    head_kv = jnp.arange(h) // (h // kv)
    own = head_kv[:, None] == jnp.arange(kv)[None, :]      # [H, KV]
    q_bd = jnp.where(own[None, :, :, None], q[:, :, None, :],
                     0).reshape(b, h, kvd)
    kernel = functools.partial(
        _pa_kernel, scale=1.0 / math.sqrt(d) if scale is None else scale,
        softcap=softcap, window=window, page=page, ppb=ppb)
    per_b = lambda bi, tbl, ctx, ly: (bi, 0, 0)
    out, m, l = pl.pallas_call(
        kernel,
        name="_pa_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, h, kvd), per_b),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            # stats are [B, H, 1]: the last two block dims must be
            # (8, 128)-aligned or span the array, which (1, H) is not
            out_specs=[
                pl.BlockSpec((1, h, kvd), per_b),
                pl.BlockSpec((1, h, 1), per_b),
                pl.BlockSpec((1, h, 1), per_b),
            ],
            scratch_shapes=[
                pltpu.VMEM((2, ppb * page, kvd), k_pool.dtype),
                pltpu.VMEM((2, ppb * page, kvd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((2,), jnp.int32),
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
        # a lane hands its successor the first block's copies: in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(block_table, ctx_lens, layer, q_bd, k_pool, v_pool)
    out = out.reshape(b, h, kv, d)[:, jnp.arange(h), head_kv]   # [B, H, D]
    if return_stats:
        return out, (m[..., 0], l[..., 0])
    return out
