"""Pallas TPU Mamba-2 decode state update: one token per lane.

A decode step of a Mamba-2 layer reads and writes each lane's whole SSM
state, [H, P, N] in f32 (4 MiB at 128 heads x 64 x 128), for a handful
of FLOPs per element, so the update is bound by HBM and is written as
one pass over the state: per head, decay by exp(dt A), add the rank-1
term (dt x) B^T, and read y = state C + D x on the way out.

The state is the decode scan's carried stack, [L, Bt, H, P, N], with
the layer to update as a scalar-prefetch operand; it is aliased to the
state output, so only that layer's blocks move and the rest stay in
place (the KV pool's scheme, kernels/paged_attention.py). Grid = (lane,
head block): each step holds a block of ``hb`` heads, about 1 MiB, in
VMEM. y leaves the kernel lane-dense as [Bt, 1, H*P] (one row of
``hb*P`` per step) and is cut back to [Bt, H, P] outside.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 1 << 20


def _ssm_step_kernel(layer_ref, s_ref, x_ref, dt_ref, a_ref, b_ref, c_ref,
                     d_ref, s_out, y_ref):
    del layer_ref                                   # read by the index maps
    s = s_ref[...]                                  # [hb, P, N]
    x = x_ref[...]                                  # [hb, P]
    da = jnp.exp(dt_ref[...] * a_ref[...])          # [hb, 1]
    dtx = dt_ref[...] * x                           # [hb, P]
    s = s * da[:, :, None] + dtx[:, :, None] * b_ref[...][None]
    s_out[...] = s
    y = jnp.sum(s * c_ref[...][None], axis=-1)      # [hb, P]
    y_ref[...] = y + d_ref[...] * x


def ssm_step(state, x, dt, A, B, C, D, *, layer=None, interpret=False):
    """state [Bt,H,P,N] f32, or the stack's [L,Bt,H,P,N] with ``layer``
    the one to update; x [Bt,H,P]; dt [Bt,H]; A, D [H]; B, C [Bt,N].
    Returns (y [Bt,H,P] in x's dtype, state)."""
    one = layer is None
    if one:
        state, layer = state[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    _, bt, h, p, n = state.shape
    hb = max(1, min(h, BLOCK_BYTES // (p * n * 4)))
    while h % hb:
        hb -= 1
    f32 = jnp.float32
    col = lambda v: v.astype(f32).reshape(h, 1)
    state_of = lambda b, j, ly: (ly[0], b, j, 0, 0)
    lane_heads = lambda b, j, ly: (b, j, 0)
    heads = lambda b, j, ly: (j, 0)
    lane = lambda b, j, ly: (b, 0, 0)
    s_new, y = pl.pallas_call(
        _ssm_step_kernel,
        name="_ssm_step_kernel",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bt, h // hb),
            in_specs=[
                pl.BlockSpec((None, None, hb, p, n), state_of),
                pl.BlockSpec((None, hb, p), lane_heads),
                pl.BlockSpec((None, hb, 1), lane_heads),
                pl.BlockSpec((hb, 1), heads),
                pl.BlockSpec((None, 1, n), lane),
                pl.BlockSpec((None, 1, n), lane),
                pl.BlockSpec((hb, 1), heads),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hb, p, n), state_of),
                pl.BlockSpec((None, hb, p), lane_heads),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct(state.shape, f32),
                   jax.ShapeDtypeStruct((bt, h, p), f32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(layer, state, x.astype(f32), dt.astype(f32).reshape(bt, h, 1), col(A),
      B.astype(f32).reshape(bt, 1, n), C.astype(f32).reshape(bt, 1, n),
      col(D))
    return y.astype(x.dtype), (s_new[0] if one else s_new)
