"""Mistral in its Hugging Face naming: the dense decoder of dense.py."""
from __future__ import annotations

from dense import *  # noqa: F401,F403  (the family interface)
from dims import Dims


def dims(c: dict) -> Dims:
    assert c.get("sliding_window") is None, "full attention expected"
    return Dims(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], qkv_bias=False,
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        max_ctx=c["max_position_embeddings"])
