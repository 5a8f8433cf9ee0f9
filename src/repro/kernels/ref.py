"""Pure-jnp oracles and blocked (memory-frugal) reference engines.

Two tiers per op:
  * ``*_naive``   — smallest possible oracle, O(S^2) memory, used only in
                    tests as ground truth.
  * ``*_blocked`` — chunked/online-softmax jnp implementation with the
                    same tiling structure as the Pallas kernel. Used (a)
                    as the CPU/dry-run lowering (realistic FLOPs + memory
                    in the compiled HLO) and (b) as the oracle for the
                    Pallas kernels at larger shapes.

Conventions: activations are [B, S, H, D] ("BSHD"); KV may have fewer
heads (GQA) and is broadcast by grouping. Softmax statistics in fp32.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap and cap > 0.0:
        return cap * jnp.tanh(x / cap)
    return x


def _group_kv(k: jnp.ndarray, n_heads: int) -> jnp.ndarray:
    """[B,S,KV,D] -> [B,S,H,D] by repeating each kv head H/KV times."""
    b, s, kv, d = k.shape
    if kv == n_heads:
        return k
    rep = n_heads // kv
    return jnp.repeat(k, rep, axis=2)


# ======================================================================
# Full attention — naive oracle
# ======================================================================
def _scale(scale, d: int) -> float:
    """The q.k multiplier: ``scale``, or 1/sqrt(head_dim) when None."""
    return 1.0 / math.sqrt(d) if scale is None else scale


def attention_naive(q, k, v, *, causal=True, window=0, softcap=0.0,
                    segment_ids=None, bidirectional=False, scale=None):
    """q [B,Sq,H,D]; k,v [B,Skv,KV,D] -> [B,Sq,H,D]. fp32 math."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    kf = _group_kv(k, h).astype(jnp.float32)
    vf = _group_kv(v, h).astype(jnp.float32)
    qf = q.astype(jnp.float32) * _scale(scale, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    logits = _softcap(logits, softcap)
    qpos = jnp.arange(sq)[:, None] + (skv - sq)  # right-aligned query positions
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), dtype=bool)
    if causal and not bidirectional:
        mask &= kpos <= qpos
    if window and window > 0:
        mask &= kpos > qpos - window
    mask_b = jnp.broadcast_to(mask[None, None], logits.shape)
    if segment_ids is not None:
        seg_q, seg_k = segment_ids
        smask = seg_q[:, None, :, None] == seg_k[:, None, None, :]
        mask_b = mask_b & smask
    logits = jnp.where(mask_b, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(q.dtype)


# ======================================================================
# Full attention — blocked flash (scan over kv chunks per q chunk)
# ======================================================================
def _online_block(carry, qf, kc, vc, mask):
    """One online-softmax accumulation step. qf [T,D] (pre-scaled fp32),
    kc/vc [C,D] fp32, mask [T,C] bool. carry = (m, l, acc)."""
    m, l, acc = carry
    s = qf @ kc.T                       # [T, C]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[:, None] + p @ vc
    return (m_new, l, acc)


def _online_block_softcap(carry, qf, kc, vc, mask, softcap):
    m, l, acc = carry
    s = qf @ kc.T
    s = _softcap(s, softcap)
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[:, None])
    l = l * alpha + p.sum(axis=-1)
    acc = acc * alpha[:, None] + p @ vc
    return (m_new, l, acc)


def flash_attention_blocked(q, k, v, *, causal=True, window=0, softcap=0.0,
                            segment_ids=None, bidirectional=False,
                            scale=None, q_chunk=512, kv_chunk=512):
    """Triangular-work blocked attention.

    Python loop over query chunks gives each chunk a *static* KV extent
    (no wasted masked FLOPs in the compiled HLO); a lax.scan over KV
    chunks inside keeps live memory at O(q_chunk * kv_chunk).
    """
    b, sq, h, d = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    if sq % q_chunk or skv % kv_chunk:
        return attention_naive(q, k, v, causal=causal, window=window,
                               softcap=softcap, segment_ids=segment_ids,
                               bidirectional=bidirectional, scale=scale)
    kf = _group_kv(k, h).astype(jnp.float32)
    vf = _group_kv(v, h).astype(jnp.float32)
    qf = q.astype(jnp.float32) * _scale(scale, d)
    off = skv - sq                                   # right-aligned queries
    seg_q, seg_k = (segment_ids if segment_ids is not None else (None, None))

    def q_block(qi):
        q0 = qi * q_chunk
        qpos = q0 + jnp.arange(q_chunk) + off
        # static KV extent for this q chunk
        if causal and not bidirectional:
            hi = min(skv, q0 + q_chunk + off)
        else:
            hi = skv
        lo = 0
        if window and window > 0:
            lo = max(0, q0 + off - window + 1)
        lo = (lo // kv_chunk) * kv_chunk
        hi = -(-hi // kv_chunk) * kv_chunk
        hi = min(hi, skv)
        n_kv = (hi - lo) // kv_chunk
        qb = qf[:, q0:q0 + q_chunk]                  # [B, T, H, D]
        kb = lax.dynamic_slice_in_dim(kf, lo, hi - lo, 1)
        vb = lax.dynamic_slice_in_dim(vf, lo, hi - lo, 1)
        kb = kb.reshape(b, n_kv, kv_chunk, h, d)
        vb = vb.reshape(b, n_kv, kv_chunk, h, d)
        sq_b = seg_q[:, q0:q0 + q_chunk] if seg_q is not None else None
        sk_b = (seg_k[:, lo:hi].reshape(b, n_kv, kv_chunk)
                if seg_k is not None else None)

        def per_bh(qv, kvs, vvs, sqv, skvs):
            # qv [T,D]; kvs/vvs [n_kv, C, D]
            def step(carry, xs):
                if sqv is None:
                    kc, vc, kpos = xs
                    skc = None
                else:
                    kc, vc, kpos, skc = xs
                mask = jnp.ones((q_chunk, kv_chunk), dtype=bool)
                if causal and not bidirectional:
                    mask &= kpos[None, :] <= qpos[:, None]
                if window and window > 0:
                    mask &= kpos[None, :] > qpos[:, None] - window
                if skc is not None:
                    mask &= sqv[:, None] == skc[None, :]
                if softcap:
                    return _online_block_softcap(carry, qv, kc, vc, mask, softcap), None
                return _online_block(carry, qv, kc, vc, mask), None

            kpos_all = lo + jnp.arange(hi - lo).reshape(n_kv, kv_chunk)
            init = (jnp.full((q_chunk,), NEG_INF, jnp.float32),
                    jnp.zeros((q_chunk,), jnp.float32),
                    jnp.zeros((q_chunk, d), jnp.float32))
            xs = (kvs, vvs, kpos_all) if sqv is None else (kvs, vvs, kpos_all, skvs)
            (m, l, acc), _ = lax.scan(step, init, xs)
            return acc / jnp.maximum(l, 1e-30)[:, None]

        fn = per_bh
        # vmap over heads then batch
        fn = jax.vmap(fn, in_axes=(1, 2, 2, None, None), out_axes=1)      # heads
        fn = jax.vmap(fn, in_axes=(0, 0, 0, 0 if sq_b is not None else None,
                                   0 if sk_b is not None else None))       # batch
        return fn(qb, kb, vb, sq_b, sk_b)            # [B, T, H, D]

    outs = [q_block(qi) for qi in range(sq // q_chunk)]
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


# ======================================================================
# Paged decode attention
# ======================================================================
def _pages(pool, layer, pages):
    """pool[pages], or pool[layer, pages] of a stacked [L, NB, ..] pool:
    the gather reads the named pages alone, never a whole layer."""
    return pool[pages] if layer is None else pool[layer, pages]


def paged_attention_naive(q, k_pool, v_pool, block_table, ctx_lens, *,
                          layer=None, softcap=0.0, window=0, page_mask=None,
                          scale=None, return_stats=False):
    """One-token decode attention over a paged KV pool.

    q           [B, H, D]
    k/v_pool    [NB, P, KV, D]   physical blocks (pages of P tokens), or
                [L, NB, P, KV, D] with ``layer`` the one to read
    block_table [B, MAXP] int32  logical page i of seq b -> physical block
    ctx_lens    [B] int32        tokens of context (including none of q)
    returns     [B, H, D]  (+ (m, l) fp32 stats if return_stats, for
                            cross-shard flash-decoding combine)
    """
    b, h, d = q.shape
    p, kv, _ = k_pool.shape[-3:]
    maxp = block_table.shape[1]
    # gather pages: [B, MAXP, P, KV, D]
    kseq = _pages(k_pool, layer, block_table).astype(jnp.float32)
    vseq = _pages(v_pool, layer, block_table).astype(jnp.float32)
    kseq = kseq.reshape(b, maxp * p, kv, d)
    vseq = vseq.reshape(b, maxp * p, kv, d)
    kseq = _group_kv(kseq, h)
    vseq = _group_kv(vseq, h)
    qf = q.astype(jnp.float32) * _scale(scale, d)
    logits = jnp.einsum("bhd,bkhd->bhk", qf, kseq)
    logits = _softcap(logits, softcap)
    pos = jnp.arange(maxp * p)[None, :]
    mask = pos < ctx_lens[:, None]
    if window and window > 0:   # sliding window: only last `window` tokens
        mask &= pos >= ctx_lens[:, None] - window
    if page_mask is not None:   # striped pools: only locally-owned pages
        mask &= jnp.repeat(page_mask, p, axis=1)
    logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    m = logits.max(axis=-1)
    pexp = jnp.exp(logits - m[..., None])
    l = pexp.sum(axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", pexp, vseq) / jnp.maximum(l, 1e-30)[..., None]
    if return_stats:
        return out.astype(q.dtype), (m, l)
    return out.astype(q.dtype)


def paged_attention_blocked(q, k_pool, v_pool, block_table, ctx_lens, *,
                            layer=None, softcap=0.0, window=0, page_mask=None,
                            scale=None, pages_per_chunk=8,
                            return_stats=False):
    """Flash-decoding style: scan over page chunks with online softmax.
    Live memory O(pages_per_chunk * P) per (B,H). Pools as for
    paged_attention_naive."""
    b, h, d = q.shape
    p, kv, _ = k_pool.shape[-3:]
    maxp = block_table.shape[1]
    c = min(pages_per_chunk, maxp)
    if maxp % c:
        c = 1
    n_chunks = maxp // c
    qf = q.astype(jnp.float32) * _scale(scale, d)
    group = h // kv

    def per_b(qb, table_b, ctx_b, pmask_b):
        # qb [H, D]
        def step(carry, ci):
            m, l, acc = carry
            pages = lax.dynamic_slice_in_dim(table_b, ci * c, c, 0)   # [c]
            pm = (lax.dynamic_slice_in_dim(pmask_b, ci * c, c, 0)
                  if pmask_b is not None else None)
            # [c, P, KV, D] each
            kc = _pages(k_pool, layer, pages).astype(jnp.float32)
            vc = _pages(v_pool, layer, pages).astype(jnp.float32)
            kc = kc.reshape(c * p, kv, d)
            vc = vc.reshape(c * p, kv, d)
            pos = ci * (c * p) + jnp.arange(c * p)
            valid = pos < ctx_b
            if window and window > 0:
                valid &= pos >= ctx_b - window
            if pm is not None:
                valid &= jnp.repeat(pm, p)
            # logits per kv head group: q heads grouped [KV, G, D]
            qg = qb.reshape(kv, group, d)
            s = jnp.einsum("kgd,tkd->kgt", qg, kc)    # [KV, G, T]
            s = _softcap(s, softcap).reshape(h, c * p)
            s = jnp.where(valid[None, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pe = jnp.exp(s - m_new[:, None])
            l2 = l * alpha + pe.sum(axis=-1)
            pv = jnp.einsum("kgt,tkd->kgd", pe.reshape(kv, group, c * p), vc)
            acc2 = acc * alpha[:, None] + pv.reshape(h, d)
            return (m_new, l2, acc2), None

        init = (jnp.full((h,), NEG_INF, jnp.float32),
                jnp.zeros((h,), jnp.float32),
                jnp.zeros((h, d), jnp.float32))
        if n_chunks == 1:
            # skip the scan machinery: a single-chunk table is the CPU
            # decode hot path (ops.paged_attention auto-widens)
            (m, l, acc), _ = step(init, 0)
        else:
            (m, l, acc), _ = lax.scan(step, init, jnp.arange(n_chunks))
        return acc / jnp.maximum(l, 1e-30)[:, None], m, l

    if page_mask is None:
        out, m, l = jax.vmap(
            lambda a, b_, c_: per_b(a, b_, c_, None))(qf, block_table,
                                                      ctx_lens)
    else:
        out, m, l = jax.vmap(per_b)(qf, block_table, ctx_lens, page_mask)
    if return_stats:
        return out.astype(q.dtype), (m, l)
    return out.astype(q.dtype)


def combine_partial_attention(outs, ms, ls):
    """Combine per-shard flash-decoding partials along a leading axis.
    outs [K,B,H,D] (already l-normalized per shard), ms/ls [K,B,H]."""
    m = ms.max(axis=0)
    w = jnp.exp(ms - m[None]) * ls                # effective weights
    denom = w.sum(axis=0)
    out = (outs * w[..., None]).sum(axis=0) / jnp.maximum(denom, 1e-30)[..., None]
    return out


# ======================================================================
# Mamba2 SSD chunked scan
# ======================================================================
def _segsum(a):
    """a [..., L] log-decays -> [..., L, L] lower-triangular cumulative
    sums: out[i,j] = sum_{k=j+1..i} a[k] for i>=j else -inf."""
    L = a.shape[-1]
    cum = jnp.cumsum(a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]
    idx = jnp.arange(L)
    mask = idx[:, None] >= idx[None, :]
    return jnp.where(mask, diff, -jnp.inf)


def mamba_chunk_scan_naive(x, dt, A, B, C, D, *, chunk, initial_state=None):
    """Sequential-scan oracle for the SSD op.

    x  [Bt, S, H, P]   (P = head dim)
    dt [Bt, S, H]      (already softplus'd, >=0)
    A  [H]             (negative; decay = exp(dt*A))
    B  [Bt, S, N]      (single group, shared across heads)
    C  [Bt, S, N]
    D  [H]             skip
    returns y [Bt, S, H, P], final_state [Bt, H, P, N]
    """
    bt, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)

    def step(state, inp):
        xt, dtt, bt_, ct = inp                      # [H,P], [H], [N], [N]
        da = jnp.exp(dtt * Af)                      # [H]
        state = state * da[:, None, None] + jnp.einsum(
            "h,hp,n->hpn", dtt, xt, bt_)
        y = jnp.einsum("hpn,n->hp", state, ct)
        return state, y

    def per_batch(xb, dtb, bb, cb, s0):
        state, ys = lax.scan(step, s0, (xb, dtb, bb, cb))
        return ys, state

    s0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((bt, h, p, n), jnp.float32))
    ys, state = jax.vmap(per_batch)(xf, dtf, Bf, Cf, s0)
    ys = ys + xf * D.astype(jnp.float32)[None, None, :, None]
    return ys.astype(x.dtype), state


def mamba_chunk_scan_blocked(x, dt, A, B, C, D, *, chunk,
                             initial_state=None):
    """Chunked SSD (Dao & Gu 2024, Alg. 1): intra-chunk matmul form +
    inter-chunk recurrence over chunk states. Matmul-heavy -> MXU-friendly;
    identical math to the sequential oracle."""
    bt, s, h, p = x.shape
    n = B.shape[-1]
    if s % chunk:
        return mamba_chunk_scan_naive(x, dt, A, B, C, D, chunk=chunk,
                                      initial_state=initial_state)
    nc = s // chunk
    xf = x.astype(jnp.float32).reshape(bt, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bt, nc, chunk, h)
    Bf = B.astype(jnp.float32).reshape(bt, nc, chunk, n)
    Cf = C.astype(jnp.float32).reshape(bt, nc, chunk, n)
    Af = A.astype(jnp.float32)

    a = dtf * Af[None, None, None, :]               # [bt,nc,L,h] log-decay
    a = jnp.moveaxis(a, -1, 2)                      # [bt,nc,h,L]
    a_cum = jnp.cumsum(a, axis=-1)                  # within-chunk cumsum
    Lmat = jnp.exp(_segsum(a))                      # [bt,nc,h,L,L]

    # --- intra-chunk (diagonal) ---
    cb = jnp.einsum("bcln,bcmn->bclm", Cf, Bf)      # [bt,nc,L,L]
    dtx = dtf[..., None] * xf                       # dt-weighted inputs
    y_diag = jnp.einsum("bclm,bchlm,bcmhp->bclhp",
                        cb, Lmat, dtx)

    # --- chunk states ---
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum)          # [bt,nc,h,L]
    states = jnp.einsum("bchl,bcln,bclhp->bchpn",
                        decay_to_end, Bf, dtx)                # [bt,nc,h,p,n]

    # --- inter-chunk recurrence ---
    chunk_decay = jnp.exp(a_cum[..., -1])                     # [bt,nc,h]
    s0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((bt, h, p, n), jnp.float32))

    def inter(carry, inp):
        st, dec = inp                                         # [bt,h,p,n],[bt,h]
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev                                      # emit state *entering* chunk

    final, prev_states = lax.scan(inter, s0,
                                  (jnp.moveaxis(states, 1, 0),
                                   jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)             # [bt,nc,h,p,n]

    # --- inter-chunk (off-diagonal) output ---
    in_decay = jnp.exp(a_cum)                                 # decay from chunk start
    y_off = jnp.einsum("bcln,bchl,bchpn->bclhp",
                       Cf, in_decay, prev_states)

    y = (y_diag + y_off).reshape(bt, s, h, p)
    y = y + x.astype(jnp.float32) * D.astype(jnp.float32)[None, None, :, None]
    return y.astype(x.dtype), final


def mamba_decode_step(state, x, dt, A, B, C, D, *, layer=None):
    """Single-token SSD recurrence. state [Bt,H,P,N], or the stack's
    [L,Bt,H,P,N] with ``layer`` the one to update; x [Bt,H,P];
    dt [Bt,H]; B,C [Bt,N]. Returns (y [Bt,H,P], new_state)."""
    stack, state = (None, state) if layer is None else (state, state[layer])
    da = jnp.exp(dt.astype(jnp.float32) * A.astype(jnp.float32)[None, :])
    xf = x.astype(jnp.float32)
    state = state * da[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt.astype(jnp.float32), xf, B.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", state, C.astype(jnp.float32))
    y = y + xf * D.astype(jnp.float32)[None, :, None]
    if stack is not None:
        state = stack.at[layer].set(state)
    return y.astype(x.dtype), state


# ======================================================================
# FMMU batched CMT probe (the paper's hot path) — reference
# ======================================================================
def fmmu_lookup_ref(tags, valid, data, dlpns, *, entries_per_block):
    """Vectorized first-level (CMT) probe.

    tags  [S, W] int32   block id (dlpn // entries_per_block) per way
    valid [S, W] bool
    data  [S, W, E] int32 DPPN entries
    dlpns [Bq] int32     query logical page numbers (-1 = inactive slot)
    returns (hit [Bq] bool, dppn [Bq] int32, set_idx, way [Bq] int32)
    """
    n_sets, n_ways = tags.shape
    block_id = dlpns // entries_per_block
    offset = dlpns % entries_per_block
    set_idx = block_id % n_sets
    active = dlpns >= 0
    way_tags = tags[set_idx]                       # [Bq, W]
    way_valid = valid[set_idx]
    match = (way_tags == block_id[:, None]) & way_valid
    hit = match.any(axis=1) & active
    way = jnp.argmax(match, axis=1).astype(jnp.int32)
    dppn = data[set_idx, way, offset]
    dppn = jnp.where(hit, dppn, -1)
    return hit, dppn, set_idx.astype(jnp.int32), way


def fmmu_translate_ref(tags, valid, refbits, data, backing, dlpns, touch, *,
                       entries_per_block):
    """Fused translate probe: CMT probe + backing-table fallback +
    ref-bit touch in one lowering (the single-probe pipeline of
    core/fmmu/batch.translate_batch).

    tags    [S, W] int32   block id (dlpn // entries_per_block) per way
    valid   [S, W] bool
    refbits [S, W] bool    second-chance reference bits
    data    [S, W, E] int32 DPPN entries
    backing [NP] int32     full flat map table (flash-resident pages)
    dlpns   [Bq] int32     query DLPNs (-1 = inactive slot)
    touch   [Bq] bool      lanes whose hit should set the ref bit
    returns (hit [Bq] bool, out [Bq] int32, set_idx, way [Bq] int32,
             refbits' [S, W] bool)

    ``out`` is the pre-call mapping: the cached DPPN on a hit, the
    backing-table entry on an active miss, NIL on inactive lanes.
    """
    n_sets, n_ways = tags.shape
    block_id = dlpns // entries_per_block
    offset = jnp.mod(dlpns, entries_per_block)
    set_idx = jnp.mod(block_id, n_sets).astype(jnp.int32)
    active = dlpns >= 0
    way_tags = tags[set_idx]                       # [Bq, W]
    way_valid = valid[set_idx]
    match = (way_tags == block_id[:, None]) & way_valid
    hit = match.any(axis=1) & active
    way = jnp.argmax(match, axis=1).astype(jnp.int32)
    cached = data[set_idx, way, offset]
    backing_val = backing[jnp.clip(dlpns, 0, backing.shape[0] - 1)]
    out = jnp.where(hit, cached, jnp.where(active, backing_val, -1))
    flat = jnp.where(hit & touch, set_idx * n_ways + way, n_sets * n_ways)
    new_ref = refbits.reshape(-1).at[flat].set(True, mode="drop").reshape(
        refbits.shape)
    return hit, out.astype(jnp.int32), set_idx, way, new_ref
