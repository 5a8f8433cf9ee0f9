"""Microbenchmarks of the compute kernels (CPU: blocked-jnp lowering —
the same graphs the dry-run compiles; Mosaic timing requires real TPU)
and of the batched FMMU translation engine (fused single-probe
translate pipeline vs the unfused pre-fusion sequence)."""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core.fmmu import batch as B
from repro.core.fmmu.types import (COND_UPDATE, LOOKUP, UPDATE,
                                   FMMUGeometry, small_geometry)
from repro.kernels import ops


def _time(fn, *args, iters=5, **kw):
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _time_state(step, st, iters=20):
    """Time a state-threading FMMU step: jitted closures DONATE the
    state buffer, so each call must consume the previous call's
    output rather than reuse a stale (already-donated) argument."""
    st = step(st)                 # warmup + compile
    jax.block_until_ready(st)
    t0 = time.perf_counter()
    for _ in range(iters):
        st = step(st)
    jax.block_until_ready(st)
    return (time.perf_counter() - t0) / iters * 1e6, st


def main():
    k = jax.random.key(0)
    # flash attention (train-ish tile)
    b, s, h, kv, d = 1, 2048, 8, 2, 64
    q = jax.random.normal(k, (b, s, h, d), jnp.bfloat16)
    kk = jax.random.normal(k, (b, s, kv, d), jnp.bfloat16)
    v = jax.random.normal(k, (b, s, kv, d), jnp.bfloat16)
    fa = jax.jit(lambda q, kk, v: ops.flash_attention(q, kk, v, impl="blocked"))
    us = _time(fa, q, kk, v)
    flops = 4 * b * h * d * s * s / 2
    emit("kernel_flash_attention_2k", us, f"{flops / us / 1e3:.1f} GFLOP/s cpu")

    # paged decode attention
    nb, p = 512, 64
    qd = jax.random.normal(k, (8, h, d), jnp.bfloat16)
    kp = jax.random.normal(k, (nb, p, kv * d), jnp.bfloat16)
    vp = jax.random.normal(k, (nb, p, kv * d), jnp.bfloat16)
    table = jnp.tile(jnp.arange(64)[None], (8, 1))
    ctx = jnp.full((8,), 64 * p - 3)
    pa = jax.jit(lambda *a: ops.paged_attention(*a, impl="blocked"))
    us = _time(pa, qd, kp, vp, table, ctx)
    emit("kernel_paged_attention_4kctx", us, "8 seqs x 4096 ctx decode")

    # mamba chunk scan
    x = jax.random.normal(k, (2, 2048, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(k, (2, 2048, 8)))
    A = -jnp.exp(jax.random.normal(k, (8,)))
    Bm = jax.random.normal(k, (2, 2048, 16))
    C = jax.random.normal(k, (2, 2048, 16))
    D = jnp.ones((8,))
    ms = jax.jit(lambda *a: ops.mamba_chunk_scan(*a, chunk=256, impl="blocked")[0])
    us = _time(ms, x, dt, A, Bm, C, D)
    emit("kernel_mamba_scan_2k", us, "2x2048 SSD chunked")

    # batched FMMU translate (the paper's hot path, vectorized)
    g = FMMUGeometry(cmt_sets=512, cmt_ways=4, cmt_entries=8,
                     ctp_sets=16, ctp_ways=4, entries_per_tp=4096,
                     n_tvpns=256, queue_cap=64)
    bq = 512
    st = B.init_batch_state(g)
    fns = B.make_jitted(g)
    dl = jax.random.randint(k, (bq,), 0, g.n_tvpns * g.entries_per_tp)
    st = fns["update"](st, dl, dl)
    us, st = _time_state(lambda s_: fns["lookup"](s_, dl)[0], st)
    emit("kernel_fmmu_lookup_512", us,
         f"{bq / us:.1f} translations/us vectorized "
         f"(paper FSM: 1 per 0.16us)")

    # fused mixed-op translate (one probe + one insert for the whole
    # LOOKUP/UPDATE/COND_UPDATE mix) vs the unfused pre-fusion sequence
    # (one call per op kind; CondUpdate alone re-probes + re-inserts)
    kb = jax.random.key(1)
    opc = jnp.asarray([LOOKUP] * (bq // 2) + [UPDATE] * (bq // 4)
                      + [COND_UPDATE] * (bq // 4), jnp.int32)
    opc = jax.random.permutation(kb, opc)
    dl2 = jax.random.permutation(
        kb, g.n_tvpns * g.entries_per_tp)[:bq].astype(jnp.int32)
    dp2 = jax.random.randint(jax.random.fold_in(kb, 1), (bq,), 0, 10 ** 6)
    old2 = jax.random.randint(jax.random.fold_in(kb, 2), (bq,), 0, 10 ** 6)
    old2 = jnp.where(jax.random.bernoulli(jax.random.fold_in(kb, 3), 0.5,
                                          (bq,)), dp2, old2)  # ~half apply
    ml, mu, mc = (opc == LOOKUP), (opc == UPDATE), (opc == COND_UPDATE)
    dll, dlu, dlc = dl2[ml], dl2[mu], dl2[mc]
    dpu, dpc, oldc = dp2[mu], dp2[mc], old2[mc]

    st = B.init_batch_state(g)
    st = fns["update"](st, dl2, dp2)
    us_fused, st = _time_state(
        lambda s_: fns["translate"](s_, opc, dl2, dp2, old2)[0], st)

    # baseline donates too: the ratio must measure fusion, not the
    # state-copy elimination donation buys both paths equally
    lu = jax.jit(functools.partial(B.lookup_batch_unfused, g),
                 donate_argnums=(0,))
    uu = jax.jit(functools.partial(B.update_batch_unfused, g),
                 donate_argnums=(0,))
    cu = jax.jit(functools.partial(B.cond_update_batch_unfused, g),
                 donate_argnums=(0,))

    def legacy_seq(s_):
        s_, _ = lu(s_, dll)
        s_ = uu(s_, dlu, dpu)
        s_, _ = cu(s_, dlc, dpc, oldc)
        return s_

    st2 = B.init_batch_state(g)
    st2 = fns["update"](st2, dl2, dp2)
    us_legacy, _ = _time_state(legacy_seq, st2)
    emit("fmmu_translate_mixed_512", us_fused,
         f"{us_legacy / us_fused:.2f}x vs unfused 3-call sequence "
         f"({us_legacy:.1f}us); lookup-only {us:.1f}us")


if __name__ == "__main__":
    main()
