"""Operations and bytes that the served dense decoder needs, from its
sizes (the dense families' counts; another family brings its own).

The arithmetic follows the usual forward count (2 FLOPs per weight per
token, plus attention over the real context): a decode token at context
``c`` (it attends ``c`` keys, itself included) costs

    2 * N_layers + n_layers * 4 * H * hd * c + 2 * d * V

where N_layers is the weight count of the decoder layers (embedding
lookups cost nothing, and the head runs once per emitted token). A
prefill of ``P`` tokens runs every layer over P tokens with causal
attention (key counts 1..P) and the head once, for the last position.
Nothing here depends on how the program computes it: paged attention is
charged the live KV it must read, never the page bucket it DMAs.
"""
from __future__ import annotations

from dims import Dims

BF16 = 2


def layer_weights(d: Dims) -> int:
    """Weights of one decoder layer (norm vectors included)."""
    attn = d.d_model * (d.n_heads + 2 * d.n_kv_heads) * d.head_dim \
        + d.n_heads * d.head_dim * d.d_model
    bias = (d.n_heads + 2 * d.n_kv_heads) * d.head_dim if d.qkv_bias else 0
    return attn + bias + 3 * d.d_model * d.d_ff + 2 * d.d_model


def matmul_flops_per_token(d: Dims) -> int:
    """Projection and MLP FLOPs of all decoder layers for one token."""
    per_layer = 2 * (d.d_model * (d.n_heads + 2 * d.n_kv_heads) * d.head_dim
                     + d.n_heads * d.head_dim * d.d_model
                     + 3 * d.d_model * d.d_ff)
    return d.n_layers * per_layer


def head_flops(d: Dims) -> int:
    return 2 * d.d_model * d.vocab


def attn_flops(d: Dims, keys: int) -> int:
    """QK^T and PV of one query over ``keys`` keys, all layers."""
    return d.n_layers * 4 * d.n_heads * d.head_dim * keys


def decode_flops(d: Dims, keys: int) -> int:
    return matmul_flops_per_token(d) + attn_flops(d, keys) + head_flops(d)


def decode_run_flops(d: Dims, first_keys: int, n: int) -> int:
    """``n`` consecutive decode tokens of one request, the first at
    ``first_keys`` keys: closed form of the sum of decode_flops."""
    if n <= 0:
        return 0
    keys = n * first_keys + n * (n - 1) // 2
    return n * (matmul_flops_per_token(d) + head_flops(d)) \
        + d.n_layers * 4 * d.n_heads * d.head_dim * keys


def prefill_flops(d: Dims, p: int) -> int:
    return p * matmul_flops_per_token(d) \
        + d.n_layers * 4 * d.n_heads * d.head_dim * (p * (p + 1) // 2) \
        + head_flops(d)


def paged_attn_bytes(d: Dims, keys: int) -> int:
    """HBM bytes one decode query needs from the paged-attention kernel,
    all layers: the live K and V rows of its context, its q, its out."""
    kv = 2 * keys * d.n_kv_heads * d.head_dim * BF16
    qo = 2 * d.n_heads * d.head_dim * BF16
    return d.n_layers * (kv + qo)


def paged_attn_run(d: Dims, first_keys: int, n: int):
    """(flops, bytes) of the paged-attention kernel over ``n``
    consecutive decode tokens starting at ``first_keys`` keys."""
    if n <= 0:
        return 0, 0
    keys = n * first_keys + n * (n - 1) // 2
    flops = d.n_layers * 4 * d.n_heads * d.head_dim * keys
    nbytes = d.n_layers * (2 * keys * d.n_kv_heads * d.head_dim * BF16
                           + n * 2 * d.n_heads * d.head_dim * BF16)
    return flops, nbytes
