"""Mamba2 (SSD) mixer block: projections + causal depthwise conv +
chunked selective-state-space scan + gated RMSNorm.

Projections are stored separately (wx/wz/wB/wC/wdt) instead of one fused
in_proj so each piece can carry its own sharding spec (d_inner and heads
shard over 'model'; the group-shared B/C projections replicate).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import ops
from repro.models import common
from repro.models.common import Runtime


def init_ssm(key, cfg, dtype):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    n = s.d_state
    ks = jax.random.split(key, 8)
    # dt bias init so softplus(dt) spans [dt_min, dt_max] (mamba default)
    dt = jnp.exp(jax.random.uniform(ks[6], (nh,), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    return {
        "wx": common.init_dense(ks[0], d, di, dtype),
        "wz": common.init_dense(ks[1], d, di, dtype),
        "wB": common.init_dense(ks[2], d, n, dtype),
        "wC": common.init_dense(ks[3], d, n, dtype),
        "wdt": common.init_dense(ks[4], d, nh, dtype),
        "conv_w": (jax.random.normal(ks[5], (s.conv_dim, di + 2 * n),
                                     jnp.float32) / math.sqrt(s.conv_dim)
                   ).astype(dtype),
        "conv_b": jnp.zeros((di + 2 * n,), dtype),
        "A_log": jnp.log(1.0 + jax.random.uniform(ks[7], (nh,), jnp.float32) * 15.0),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_bias,
        "norm": common.init_rms_norm(di, dtype),
        "wo": common.init_dense(jax.random.fold_in(key, 99), di, d, dtype),
    }


def ssm_specs(cfg):
    return {
        "wx": P(None, "model"),
        "wz": P(None, "model"),
        "wB": P(None, None),
        "wC": P(None, None),
        "wdt": P(None, "model"),
        "conv_w": P(None, None),
        "conv_b": P(None,),
        "A_log": P("model",),
        "D": P("model",),
        "dt_bias": P("model",),
        "norm": P("model",),
        "wo": P("model", None),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv via shifted adds. x [B,S,C]; w [K,C]."""
    k = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    s = x.shape[1]
    y = sum(pad[:, i:i + s] * w[i][None, None, :] for i in range(k))
    return jax.nn.silu(y + b[None, None, :])


def _conv_step(state, x_new, w, b):
    """state [B,K-1,C]; x_new [B,C] -> (y [B,C], new_state)."""
    window = jnp.concatenate([state, x_new[:, None]], axis=1)   # [B,K,C]
    y = jnp.einsum("bkc,kc->bc", window, w)
    return jax.nn.silu(y + b[None, :]), window[:, 1:]


def _project(params, x, cfg, rt: Runtime):
    cd = rt.compute_dtype
    xb = x @ common.cast(params["wx"], cd)
    z = x @ common.cast(params["wz"], cd)
    bv = x @ common.cast(params["wB"], cd)
    cv = x @ common.cast(params["wC"], cd)
    dt = x @ common.cast(params["wdt"], cd)
    return xb, z, bv, cv, dt


def ssm_forward(params, x, cfg, rt: Runtime, *, initial_state=None,
                return_state=False):
    """Train/prefill path. x [B,S,d] -> [B,S,d] (+ (conv_state, ssm_state))."""
    s = cfg.ssm
    b, sl, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    n = s.d_state
    xb, z, bv, cv, dt = _project(params, x, cfg, rt)
    conv_in = jnp.concatenate([xb, bv, cv], axis=-1)
    conv_out = _causal_conv(conv_in, params["conv_w"].astype(rt.compute_dtype),
                            params["conv_b"].astype(rt.compute_dtype))
    xb, bv, cv = (conv_out[..., :di], conv_out[..., di:di + n],
                  conv_out[..., di + n:])
    dtv = jax.nn.softplus(dt.astype(jnp.float32)
                          + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])
    y, final = ops.mamba_chunk_scan(
        xb.reshape(b, sl, nh, s.head_dim), dtv, A, bv, cv, params["D"],
        chunk=s.chunk, initial_state=initial_state, impl=rt.kernel_impl)
    y = y.reshape(b, sl, di)
    y = common.rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                        params["norm"], cfg.norm_eps)
    out = y @ common.cast(params["wo"], rt.compute_dtype)
    if return_state:
        k = s.conv_dim - 1
        conv_state = jnp.pad(conv_in, ((0, 0), (k, 0), (0, 0)))[:, -k:]
        return out, (conv_state.astype(rt.compute_dtype), final)
    return out


def ssm_init_state(cfg, batch: int, dtype):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    conv_state = jnp.zeros((batch, s.conv_dim - 1, di + 2 * s.d_state), dtype)
    ssm_state = jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32)
    return conv_state, ssm_state


def ssm_decode(params, x, state, cfg, rt: Runtime, layer):
    """One-token decode. x [B,d]; state=(conv_state, ssm_state), the
    decode scan's stacks [L, B, ...]; ``layer`` is the one this call
    reads and updates (in place: the ``ssm_state`` scope)."""
    s = cfg.ssm
    b, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    n = s.d_state
    conv_state, ssm_state = state
    xb, z, bv, cv, dt = _project(params, x[:, None, :], cfg, rt)
    conv_in = jnp.concatenate([xb[:, 0], bv[:, 0], cv[:, 0]], axis=-1)
    conv_out, conv_new = _conv_step(
        conv_state[layer], conv_in,
        params["conv_w"].astype(rt.compute_dtype),
        params["conv_b"].astype(rt.compute_dtype))
    xb1, bv1, cv1 = (conv_out[:, :di], conv_out[:, di:di + n],
                     conv_out[:, di + n:])
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"][None, :])
    A = -jnp.exp(params["A_log"])
    with jax.named_scope("ssm_state"):
        y, ssm_state = ops.mamba_decode_step(
            ssm_state, xb1.reshape(b, nh, s.head_dim), dtv, A, bv1, cv1,
            params["D"], layer=layer, impl=rt.kernel_impl)
        conv_state = conv_state.at[layer].set(conv_new)
    y = y.reshape(b, di)
    y = common.rms_norm(y * jax.nn.silu(z[:, 0].astype(jnp.float32)).astype(y.dtype),
                        params["norm"], cfg.norm_eps)
    out = y @ common.cast(params["wo"], rt.compute_dtype)
    return out, (conv_state, ssm_state)
