"""The dense decoder (GLM-4 and Mistral as configured) behind the
family interface (harness.load_family): the program's architecture, and
the weights, reference and counts that weights.py, reference.py and
flops.py hold once for every dense family. ``families/chatglm.py`` and
``families/mistral.py`` import all of it and add ``dims``, the reader of
their own config.json keys.
"""
from __future__ import annotations

import flops
import reference
import weights
from dims import Dims

__all__ = ["arch", "to_program", "layer_kinds", "make_layer",
           "make_outer", "forward_layer", "embed", "head", "prefill_flops",
           "decode_run_flops", "paged_attn_run"]

to_program, make_outer = weights.to_program, weights.make_outer
embed, head = reference.embed, reference.head
prefill_flops, decode_run_flops, paged_attn_run = \
    flops.prefill_flops, flops.decode_run_flops, flops.paged_attn_run


def arch(d: Dims, name: str):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=name, family="dense",
        n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
        n_kv_heads=d.n_kv_heads, head_dim=d.head_dim, d_ff=d.d_ff,
        vocab_size=d.vocab, qkv_bias=d.qkv_bias, rope_theta=d.rope_theta,
        norm_eps=d.norm_eps)


def layer_kinds(d: Dims):
    return ["dense"] * d.n_layers


def make_layer(key, i, d: Dims, kind: str):
    return weights.make_layer(key, i, d)


def forward_layer(x, w, pos, d: Dims, kind: str, fp8: bool, q_block: int):
    return reference._layer_fwd(x, w, pos, d, fp8, q_block)
