"""Pallas TPU Mamba2 SSD chunked scan.

Grid = (batch, heads, chunks); the chunk axis is minor-most and carries
the inter-chunk SSM state [head_dim, d_state] in VMEM scratch — the
sequential recurrence collapses to one small FMA per chunk while all
intra-chunk work is dense matmuls on (chunk x chunk) / (chunk x P/N)
tiles, keeping the MXU busy (the SSD duality). Chunk=256 with P=64,
N=128 gives tiles of at most 256x256 — a few hundred KB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ms_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s0_ref,
               y_ref, fin_ref, state_sc, *, chunk, has_init):
    hh = pl.program_id(1)
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        if has_init:
            state_sc[...] = s0_ref[...].astype(jnp.float32)
        else:
            state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[...].astype(jnp.float32)              # [L, P]
    dt = dt_ref[...].astype(jnp.float32)            # [1, L]
    A = a_ref[hh]                                   # scalar (SMEM)
    B = b_ref[...].astype(jnp.float32)              # [L, N]
    C = c_ref[...].astype(jnp.float32)              # [L, N]
    D = d_ref[hh]

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # within-chunk cumulative log-decay, as a row and as a column
    a_cum = jax.lax.dot(dt * A, (ii <= jj).astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)   # [1, L]
    a_col = a_cum.T                                 # [L, 1]
    a_end = a_cum[:, chunk - 1:]                    # [1, 1]
    # lower-triangular decay matrix L[i,j] = exp(a_cum[i]-a_cum[j]) i>=j
    Lmat = jnp.where(ii >= jj, jnp.exp(a_col - a_cum), 0.0)

    dtx = dt.T * x                                  # [L, P]
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [L, L]
    y_diag = jax.lax.dot((cb * Lmat), dtx,
                         preferred_element_type=jnp.float32)      # [L, P]

    state = state_sc[...]                           # [P, N]
    y_off = jax.lax.dot_general(C, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [L, P]
    y_off = y_off * jnp.exp(a_col)                  # decay from chunk start

    y_ref[...] = (y_diag + y_off + D * x).astype(y_ref.dtype)

    # chunk state update: S = S * exp(sum a) + sum_j exp(a_end - a_j) dtx_j B_j^T
    w = (dtx * jnp.exp(a_end - a_col)).T            # [P, L]
    S_new = jax.lax.dot(w, B, preferred_element_type=jnp.float32)  # [P, N]
    # the chunk's whole decay as a [1, N] row (Mosaic cannot broadcast
    # a [1, 1] over sublanes and lanes at once)
    a_sum = jax.lax.dot(dt * A, jnp.ones((chunk, state.shape[1]), jnp.float32),
                        precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)   # [1, N]
    state_sc[...] = state * jnp.exp(a_sum) + S_new

    @pl.when(ci == nc - 1)
    def _finish():
        fin_ref[...] = state_sc[...]


def mamba_chunk_scan(x, dt, A, B, C, D, *, chunk=256, initial_state=None,
                     interpret=False):
    """x [Bt,S,H,P]; dt [Bt,S,H]; A [H]; B,C [Bt,S,N]; D [H].
    Returns (y [Bt,S,H,P], final_state [Bt,H,P,N]).

    The kernel sees heads as a leading axis: x and y move as [Bt,H,S,P]
    and dt as [Bt,H,1,S] (a lane-dense row per chunk), so every block's
    last two dims are (8, 128)-tiled or whole; A and D sit in SMEM."""
    bt, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, "pad sequence to chunk multiple"
    nc = s // chunk
    has_init = initial_state is not None
    s0 = (initial_state if has_init
          else jnp.zeros((bt, h, p, n), jnp.float32))
    kernel = functools.partial(_ms_kernel, chunk=chunk, has_init=has_init)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    state_of = lambda b, hh, c: (b, hh, 0, 0)
    y, fin = pl.pallas_call(
        kernel,
        name="_ms_kernel",
        grid=(bt, h, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, 1, chunk), lambda b, hh, c: (b, hh, 0, c)),
            smem,
            pl.BlockSpec((None, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, n), lambda b, hh, c: (b, c, 0)),
            smem,
            pl.BlockSpec((None, None, p, n), state_of),
        ],
        out_specs=[
            pl.BlockSpec((None, None, chunk, p), lambda b, hh, c: (b, hh, c, 0)),
            pl.BlockSpec((None, None, p, n), state_of),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bt, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[:, :, None, :],
      A.astype(jnp.float32), B, C, D.astype(jnp.float32), s0)
    return y.transpose(0, 2, 1, 3), fin
