"""Bare batched CMT probe (probe-only, no side effects).

The probe primitive for the unfused reference path
(`core/fmmu/batch.*_unfused`, equivalence tests + benchmarks). It runs
the fused translate kernel (`fmmu_translate.py`) with no ref-bit touch
and a one-entry all-NIL backing table, so a miss reads NIL: one Pallas
kernel serves both, and what compiles for the TPU there compiles here.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.fmmu_translate import fmmu_translate


def fmmu_lookup(tags, valid, data, dlpns, *, entries_per_block,
                block_size=256, interpret=False):
    """tags [S,W] int32; valid [S,W] bool; data [S,W,E] int32;
    dlpns [Bq] int32 -> (hit bool, dppn, set, way)."""
    hit, dppn, set_idx, way, _ = fmmu_translate(
        tags, valid, jnp.zeros_like(valid), data,
        jnp.full((1,), -1, jnp.int32), dlpns,
        jnp.zeros(dlpns.shape, bool), entries_per_block=entries_per_block,
        block_size=block_size, interpret=interpret)
    return hit, dppn, set_idx, way
