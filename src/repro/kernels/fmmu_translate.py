"""Pallas TPU kernel for the fused FMMU translate pipeline.

One kernel invocation services the whole probe side of a mixed-op
translate batch (core/fmmu/batch.translate_batch): CMT tag probe,
backing-table fallback for misses, ref-bit touch for hits, and hit-way
selection — where the pre-fusion path issued a probe kernel and then
fixed up misses / ref bits on the host side of the graph.

Hardware adaptation (DESIGN.md, "Fused translate pipeline"): the
paper's CAM-style parallel tag compare becomes a one-hot matmul gather
on the MXU — set indices expand to a one-hot [blk, S] matrix multiplied
against the VMEM-resident tag / data arrays (TPUs have no CAM, but they
have a 128x128 systolic array). The backing-table fallback — the
paper's flash-resident translation-page read that the FMMU overlaps
with new probes — streams through a second, chunk-sized grid
dimension: only one `backing_chunk` tile is VMEM-resident at a time,
so the table never has to fit on-chip (per-lane-block outputs are
revisited across chunk steps and accumulate the fallback value).
Like the tag CAM, this trades FLOPs for regularity — the streamed
one-hot gather is O(Bq x NP) MXU work instead of an O(Bq) random
gather, which is the right trade for CMT-scale tables on a systolic
array; a scalar-prefetch (PrefetchScalarGridSpec) gather indexed by
the miss DLPNs is the refinement path for very large tables. The
CPU/serving default (`impl="blocked"`) uses the reference lowering's
exact O(Bq) gather and is unaffected.

Value gathers (cached DPPNs, backing entries) must be bit-exact for
any int32 — the paging layer tags host-tier blocks at 1<<24 and above,
past f32's exact-integer range — so they use `gather16` (two matmuls
over the 16-bit halves, recombined in int32). Tag/set
*compares* stay in single f32: block ids are dlpn // E < 2^24 at any
supported geometry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# exact f32 contraction: an unset precision may take one bf16 MXU pass,
# which rounds block-id tags past 2^8 and 16-bit value halves
_EXACT = jax.lax.Precision.HIGHEST


def gather16(onehot, vals2d):
    """Bit-exact int32 one-hot gather on the MXU: two f32 matmuls over
    the 16-bit halves (lo = v & 0xffff in [0, 2^16), hi = v >> 16 in
    [-2^15, 2^15) — each f32-exact), recombined in int32. Needed
    because gathered values may exceed f32's 2^24 exact-integer range:
    the paging layer tags host-tier block ids at 1<<24 and above.
    onehot [r, c] f32 (exactly one 1.0 per row, or all-zero rows);
    vals2d [c, k] int32 -> [r, k] int32."""
    lo = jax.lax.dot(onehot, (vals2d & 0xffff).astype(jnp.float32),
                     precision=_EXACT, preferred_element_type=jnp.float32)
    hi = jax.lax.dot(onehot, (vals2d >> 16).astype(jnp.float32),
                     precision=_EXACT, preferred_element_type=jnp.float32)
    return hi.astype(jnp.int32) * 65536 + lo.astype(jnp.int32)


def _ft_kernel(tags_ref, valid_ref, data_ref, backing_ref, dlpn_ref,
               touch_ref, refin_ref, hit_ref, dppn_ref, set_ref, way_ref,
               refout_ref, *, entries_per_block, n_sets, n_ways,
               backing_chunk, n_backing, blk):
    # lane vectors are [blk, 1] columns and the CMT data is pre-flattened
    # to [S, W*E]: Mosaic tiles the last two dims of every block, so 1-D
    # lane blocks and in-kernel 3-D -> 2-D reshapes do not lower
    i = pl.program_id(0)      # lane block (outer)
    c = pl.program_id(1)      # backing chunk (inner, fastest)
    dlpns = dlpn_ref[...]                              # [blk, 1]
    active = dlpns >= 0

    @pl.when((i == 0) & (c == 0))
    def _init_ref():
        refout_ref[...] = refin_ref[...]

    @pl.when(c == 0)
    def _probe():
        block_id = dlpns // entries_per_block
        offset = jnp.mod(dlpns, entries_per_block)
        set_idx = jnp.mod(block_id, n_sets)
        # one-hot gather of the probe sets via the MXU
        onehot = (set_idx ==
                  jax.lax.broadcasted_iota(jnp.int32, (blk, n_sets), 1)
                  ).astype(jnp.float32)                # [blk, S]
        tags = tags_ref[...].astype(jnp.float32)       # [S, W]
        valid = valid_ref[...].astype(jnp.float32)     # [S, W]
        row_tags = jax.lax.dot(onehot, tags, precision=_EXACT,
                               preferred_element_type=jnp.float32)
        row_valid = jax.lax.dot(onehot, valid,
                                preferred_element_type=jnp.float32)
        match = ((row_tags == block_id.astype(jnp.float32)) &
                 (row_valid > 0.5)).astype(jnp.float32)  # [blk, W]
        hit = (jnp.max(match, axis=1, keepdims=True) > 0.5) & active
        way = jnp.argmax(match, axis=1, keepdims=True).astype(jnp.int32)

        e = entries_per_block
        row_data = gather16(onehot, data_ref[...])     # [blk, W*E]
        col = way * e + offset
        # one-hot select-and-reduce: bit-exact, and lowers where a
        # per-row gather (take_along_axis) does not
        picked = jnp.sum(jnp.where(jax.lax.broadcasted_iota(
            jnp.int32, (blk, n_ways * e), 1) == col, row_data, 0),
            axis=1, keepdims=True)                     # [blk, 1]

        hit_ref[...] = hit.astype(jnp.int32)
        set_ref[...] = set_idx.astype(jnp.int32)
        way_ref[...] = way
        # misses start at 0 and accumulate their backing value chunk by
        # chunk; hits are final immediately, inactive lanes stay NIL
        dppn_ref[...] = jnp.where(hit, picked,
                                  jnp.where(active, 0, -1))

        # ref-bit touch; only the selected (argmax) way is touched,
        # matching the reference lowering even on degenerate states
        # with duplicate tags in a set
        touch = (touch_ref[...] != 0) & hit            # [blk, 1]
        tmask = ((way ==
                  jax.lax.broadcasted_iota(jnp.int32, (blk, n_ways), 1))
                 & touch).astype(jnp.float32)          # [blk, W]
        acc = jax.lax.dot_general(
            onehot, tmask, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) > 0.5  # [S, W]
        refout_ref[...] = refout_ref[...] | acc.astype(jnp.int32)

    # every (i, c) step: fold this backing chunk into the miss lanes;
    # clip like the reference lowering so an out-of-contract dlpn
    # (>= NP) reads backing[NP-1] on every impl path instead of
    # silently matching nothing / the pad region
    miss = active & (hit_ref[...] == 0)
    loc = jnp.clip(dlpns, -1, n_backing - 1) - c * backing_chunk
    oh = ((loc ==
           jax.lax.broadcasted_iota(jnp.int32, (blk, backing_chunk), 1))
          & miss).astype(jnp.float32)
    dppn_ref[...] = dppn_ref[...] + gather16(oh, backing_ref[...])


def fmmu_translate(tags, valid, refbits, data, backing, dlpns, touch, *,
                   entries_per_block, block_size=256, backing_chunk=512,
                   interpret=False):
    """tags [S,W] int32; valid/refbits [S,W] bool; data [S,W,E] int32;
    backing [NP] int32; dlpns/touch [Bq] ->
    (hit bool, out_dppn, set, way, refbits' [S,W] bool)."""
    n_sets, n_ways = tags.shape
    bq = dlpns.shape[0]
    blk = min(block_size, bq)
    bq_p = -(-bq // blk) * blk
    if bq_p != bq:
        dlpns = jnp.pad(dlpns, (0, bq_p - bq), constant_values=-1)
        touch = jnp.pad(touch, (0, bq_p - bq))
    np_ = backing.shape[0]
    ch = min(backing_chunk, np_)
    np_p = -(-np_ // ch) * ch
    if np_p != np_:
        backing = jnp.pad(backing, (0, np_p - np_), constant_values=-1)
    kernel = functools.partial(
        _ft_kernel, entries_per_block=entries_per_block, n_sets=n_sets,
        n_ways=n_ways, backing_chunk=ch, n_backing=np_, blk=blk)
    full = pl.BlockSpec((n_sets, n_ways), lambda i, c: (0, 0))
    lanes = pl.BlockSpec((blk, 1), lambda i, c: (i, 0))
    we = n_ways * entries_per_block
    hit, dppn, set_idx, way, new_ref = pl.pallas_call(
        kernel,
        grid=(bq_p // blk, np_p // ch),
        in_specs=[
            full, full,
            pl.BlockSpec((n_sets, we), lambda i, c: (0, 0)),
            pl.BlockSpec((ch, 1), lambda i, c: (c, 0)),
            lanes, lanes, full,
        ],
        out_specs=[lanes] * 4 + [full],
        out_shape=[jax.ShapeDtypeStruct((bq_p, 1), jnp.int32)] * 4 +
                  [jax.ShapeDtypeStruct((n_sets, n_ways), jnp.int32)],
        interpret=interpret,
    )(tags, valid.astype(jnp.int32), data.reshape(n_sets, we),
      backing[:, None], dlpns[:, None], touch.astype(jnp.int32)[:, None],
      refbits.astype(jnp.int32))
    return (hit[:bq, 0].astype(bool), dppn[:bq, 0], set_idx[:bq, 0],
            way[:bq, 0], new_ref.astype(bool))
