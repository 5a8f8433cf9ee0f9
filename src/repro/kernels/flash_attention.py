"""Pallas TPU flash attention (causal / sliding-window / softcap, GQA).

Tiling: grid = (batch, q_heads, q_blocks, kv_blocks); the kv_blocks axis
is minor-most, so the online-softmax statistics (m, l, acc) live in VMEM
scratch carried across kv iterations. Fully-masked kv blocks (beyond the
causal frontier / outside the sliding window) are skipped with pl.when —
on hardware they cost only grid overhead. KV tiles for GQA are indexed
at kv_head = q_head // group via the BlockSpec index map, so each q-head
program DMAs only its shared KV tile. Block shapes default to
(q=512, kv=512) with full head_dim — (512, 128) tiles keep the MXU fed
and the working set (q + k + v + acc + p: ~5 * 512*128 * 4B ≈ 1.3MB)
comfortably inside the ~16MB VMEM budget.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
               scale, causal, window, softcap, q_block, kv_block, seq_kv,
               bidirectional, q_offset):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    # right-aligned query positions (cross-length causal: q row i sits at
    # absolute position i + (seq_kv - seq_q))
    qpos = q_offset + iq * q_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 0)
    kpos = ik * kv_block + jax.lax.broadcasted_iota(
        jnp.int32, (q_block, kv_block), 1)

    run = jnp.asarray(True)
    if causal and not bidirectional:
        run = run & (ik * kv_block <= q_offset + (iq + 1) * q_block - 1)
    if window and window > 0:
        run = run & ((ik + 1) * kv_block - 1 > q_offset + iq * q_block - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [qb, d]
        k = k_ref[0, 0].astype(jnp.float32)                  # [kb, d]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap and softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        mask = kpos < seq_kv
        if causal and not bidirectional:
            mask &= kpos <= qpos
        if window and window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_sc[...] = l_sc[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_sc[...] /
                       jnp.maximum(l_sc[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    segment_ids=None, bidirectional=False, scale=None,
                    q_block=512, kv_block=512, interpret=False):
    """q [B,Sq,H,D]; k,v [B,Skv,KV,D] -> [B,Sq,H,D]; ``scale``
    multiplies q.k (None: 1/sqrt(D))."""
    if segment_ids is not None:
        raise NotImplementedError(
            "segment_ids: use the blocked-jnp lowering (impl='blocked')")
    b, sq, h, d = q.shape
    skv, kv = k.shape[1], k.shape[2]
    group = h // kv
    q_block = min(q_block, sq)
    kv_block = min(kv_block, skv)
    # pad to block multiples
    sq_p = -(-sq // q_block) * q_block
    skv_p = -(-skv // kv_block) * kv_block
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_p - sq), (0, 0), (0, 0)))
    if skv_p != skv:
        k = jnp.pad(k, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, skv_p - skv), (0, 0), (0, 0)))
    qt = q.transpose(0, 2, 1, 3)      # [B,H,S,D]
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    grid = (b, h, sq_p // q_block, skv_p // kv_block)
    kernel = functools.partial(
        _fa_kernel, scale=1.0 / math.sqrt(d) if scale is None else scale,
        causal=causal, window=window,
        softcap=softcap, q_block=q_block, kv_block=kv_block, seq_kv=skv,
        bidirectional=bidirectional, q_offset=skv - sq)
    out = pl.pallas_call(
        kernel,
        name="_fa_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, q_block, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, kv_block, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, kv_block, d),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_block, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, 1), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)[:, :sq]
