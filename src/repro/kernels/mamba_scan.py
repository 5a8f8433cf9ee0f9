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
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        if has_init:
            state_sc[...] = s0_ref[0, 0].astype(jnp.float32)
        else:
            state_sc[...] = jnp.zeros_like(state_sc)

    x = x_ref[0, :, 0].astype(jnp.float32)          # [L, P]
    dt = dt_ref[0, :, 0].astype(jnp.float32)        # [L]
    A = a_ref[0].astype(jnp.float32)                # scalar
    B = b_ref[0].astype(jnp.float32)                # [L, N]
    C = c_ref[0].astype(jnp.float32)                # [L, N]
    D = d_ref[0].astype(jnp.float32)

    a = dt * A                                      # [L] log-decay
    a_cum = jnp.cumsum(a)
    # lower-triangular decay matrix L[i,j] = exp(a_cum[i]-a_cum[j]) i>=j
    diff = a_cum[:, None] - a_cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    Lmat = jnp.where(ii >= jj, jnp.exp(diff), 0.0)

    dtx = dt[:, None] * x                           # [L, P]
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [L, L]
    y_diag = jax.lax.dot((cb * Lmat), dtx,
                         preferred_element_type=jnp.float32)      # [L, P]

    state = state_sc[...]                           # [P, N]
    in_decay = jnp.exp(a_cum)                       # decay from chunk start
    y_off = jax.lax.dot(C, state.T,
                        preferred_element_type=jnp.float32)       # [L, P]
    y_off = y_off * in_decay[:, None]

    y_ref[0, :, 0] = (y_diag + y_off + D * x).astype(y_ref.dtype)

    # chunk state update: S = S * exp(sum a) + sum_j exp(a_end - a_j) dtx_j B_j^T
    decay_to_end = jnp.exp(a_cum[-1] - a_cum)       # [L]
    S_new = jax.lax.dot_general(dtx * decay_to_end[:, None], B,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [P,N]
    state_sc[...] = state * jnp.exp(a_cum[-1]) + S_new

    @pl.when(ci == nc - 1)
    def _finish():
        fin_ref[0, 0] = state_sc[...]


def mamba_chunk_scan(x, dt, A, B, C, D, *, chunk=256, initial_state=None,
                     interpret=False):
    """x [Bt,S,H,P]; dt [Bt,S,H]; A [H]; B,C [Bt,S,N]; D [H].
    Returns (y [Bt,S,H,P], final_state [Bt,H,P,N])."""
    bt, s, h, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, "pad sequence to chunk multiple"
    nc = s // chunk
    has_init = initial_state is not None
    s0 = (initial_state if has_init
          else jnp.zeros((bt, h, p, n), jnp.float32))
    kernel = functools.partial(_ms_kernel, chunk=chunk, has_init=has_init)
    y, fin = pl.pallas_call(
        kernel,
        name="_ms_kernel",
        grid=(bt, h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, hh, c: (b, c, hh)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, hh, c: (b, c, 0)),
            pl.BlockSpec((1,), lambda b, hh, c: (hh,)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda b, hh, c: (b, c, hh, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b, hh, c: (b, hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, s, h, p), x.dtype),
            jax.ShapeDtypeStruct((bt, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, A, B, C, D, s0)
    return y, fin
