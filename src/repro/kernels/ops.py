"""Jit'd dispatch wrappers for the compute hot-spots.

``impl`` selects the lowering:
  auto             — Pallas on TPU, blocked-jnp elsewhere (CPU dry-run /
                     tests). This keeps .lower().compile() working on the
                     512-virtual-device CPU mesh while targeting Mosaic
                     on real hardware.
  pallas           — pl.pallas_call, native (TPU)
  pallas_interpret — pl.pallas_call(interpret=True): kernel body
                     executed by the Pallas interpreter on CPU; used by
                     the per-kernel allclose tests.
  blocked          — chunked pure-jnp engine (same tiling as the kernel)
  naive            — O(S^2) oracle (tests only)

The Pallas attention kernels take no segment or page masks: a masked
call that selects a Pallas lowering (by name or through ``auto`` on a
TPU) raises instead of quietly running another lowering, so a caller
with masks asks for ``blocked`` (or ``naive``) by name.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _default_impl(impl: Optional[str]) -> str:
    if impl not in (None, "auto"):
        return impl
    platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "blocked"


# ----------------------------------------------------------------------
def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    segment_ids=None, bidirectional=False, impl=None,
                    q_chunk=512, kv_chunk=512):
    sel = _default_impl(impl)
    if sel in ("pallas", "pallas_interpret"):
        from repro.kernels import flash_attention as fa   # raises on segments
        return fa.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap,
            segment_ids=segment_ids, bidirectional=bidirectional,
            interpret=(sel == "pallas_interpret"))
    if sel == "blocked":
        return ref.flash_attention_blocked(
            q, k, v, causal=causal, window=window, softcap=softcap,
            segment_ids=segment_ids, bidirectional=bidirectional,
            q_chunk=q_chunk, kv_chunk=kv_chunk)
    return ref.attention_naive(q, k, v, causal=causal, window=window,
                               softcap=softcap, segment_ids=segment_ids,
                               bidirectional=bidirectional)


# ----------------------------------------------------------------------
def paged_attention(q, k_pool, v_pool, block_table, ctx_lens, *,
                    softcap=0.0, window=0, page_mask=None,
                    return_stats=False, impl=None, pages_per_chunk=None):
    """q [B,H,D]; lane-dense pools [NB,P,KV*D] (kernels/paged_attention
    .py says why); the jnp lowerings view them as [NB,P,KV,D]."""
    sel = _default_impl(impl)
    if sel in ("pallas", "pallas_interpret") and page_mask is not None:
        raise NotImplementedError(
            "paged_attention: the Pallas kernel takes no page_mask; "
            "pass impl='blocked' for masked calls")
    if sel in ("pallas", "pallas_interpret"):
        from repro.kernels import paged_attention as pa
        return pa.paged_attention(
            q, k_pool, v_pool, block_table, ctx_lens, softcap=softcap,
            window=window, return_stats=return_stats,
            interpret=(sel == "pallas_interpret"))
    nb, p, kvd = k_pool.shape
    d = q.shape[-1]
    k_pool = k_pool.reshape(nb, p, kvd // d, d)
    v_pool = v_pool.reshape(nb, p, kvd // d, d)
    if sel == "blocked":
        if pages_per_chunk is None:
            # auto: chunking bounds live memory at O(c * P) per (B,H),
            # but every chunk is a scan iteration of tiny ops — the
            # dominant CPU decode cost — so take the whole table in one
            # chunk whenever it fits a modest live window
            maxp = block_table.shape[1]
            pages_per_chunk = maxp if maxp * p <= 1024 else 8
        return ref.paged_attention_blocked(
            q, k_pool, v_pool, block_table, ctx_lens, softcap=softcap,
            window=window, page_mask=page_mask,
            pages_per_chunk=pages_per_chunk, return_stats=return_stats)
    return ref.paged_attention_naive(q, k_pool, v_pool, block_table,
                                     ctx_lens, softcap=softcap,
                                     window=window, page_mask=page_mask,
                                     return_stats=return_stats)


# ----------------------------------------------------------------------
def mamba_chunk_scan(x, dt, A, B, C, D, *, chunk=256, initial_state=None,
                     impl=None):
    sel = _default_impl(impl)
    if sel in ("pallas", "pallas_interpret"):
        from repro.kernels import mamba_scan as ms
        return ms.mamba_chunk_scan(
            x, dt, A, B, C, D, chunk=chunk, initial_state=initial_state,
            interpret=(sel == "pallas_interpret"))
    if sel == "blocked":
        return ref.mamba_chunk_scan_blocked(x, dt, A, B, C, D, chunk=chunk,
                                            initial_state=initial_state)
    return ref.mamba_chunk_scan_naive(x, dt, A, B, C, D, chunk=chunk,
                                      initial_state=initial_state)


# ----------------------------------------------------------------------
def fmmu_lookup(tags, valid, data, dlpns, *, entries_per_block, impl=None):
    sel = _default_impl(impl)
    if sel in ("pallas", "pallas_interpret"):
        from repro.kernels import fmmu_lookup as fl
        return fl.fmmu_lookup(tags, valid, data, dlpns,
                              entries_per_block=entries_per_block,
                              interpret=(sel == "pallas_interpret"))
    return ref.fmmu_lookup_ref(tags, valid, data, dlpns,
                               entries_per_block=entries_per_block)


# ----------------------------------------------------------------------
def fmmu_translate(tags, valid, refbits, data, backing, dlpns, touch, *,
                   entries_per_block, impl=None):
    """Fused translate probe (probe + backing fallback + ref touch) —
    the single kernel invocation behind core/fmmu/batch.translate_batch.
    Returns (hit, out_dppn, set_idx, way, refbits')."""
    sel = _default_impl(impl)
    if sel in ("pallas", "pallas_interpret"):
        from repro.kernels import fmmu_translate as ft
        return ft.fmmu_translate(tags, valid, refbits, data, backing,
                                 dlpns, touch,
                                 entries_per_block=entries_per_block,
                                 interpret=(sel == "pallas_interpret"))
    return ref.fmmu_translate_ref(tags, valid, refbits, data, backing,
                                  dlpns, touch,
                                  entries_per_block=entries_per_block)


combine_partial_attention = ref.combine_partial_attention
mamba_decode_step = ref.mamba_decode_step
