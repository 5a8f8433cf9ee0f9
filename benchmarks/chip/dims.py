"""Sizes of a configuration, read from its file under ``configs/``.

A configuration file holds the keys of the model's own published
``config.json`` (with the values changed that its ``reduced`` list in
BENCHMARK.json names), a ``serving`` group for the engine, and notes.
The key names differ between model families; ``dims`` reads the
families the benchmark knows and names every other one as an error.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    rope_theta: float
    norm_eps: float
    max_ctx: int


def _chatglm(c: dict) -> Dims:
    # THUDM ChatGLM/GLM-4 naming; the rope base is 10000 * rope_ratio
    assert c["rmsnorm"] and not c["add_bias_linear"], "GLM-4 form expected"
    return Dims(
        n_layers=c["num_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=(c["multi_query_group_num"] if c["multi_query_attention"]
                    else c["num_attention_heads"]),
        head_dim=c["kv_channels"], d_ff=c["ffn_hidden_size"],
        vocab=c["padded_vocab_size"], qkv_bias=bool(c["add_qkv_bias"]),
        rope_theta=10000.0 * c.get("rope_ratio", 1),
        norm_eps=c["layernorm_epsilon"], max_ctx=c["seq_length"])


def _mistral(c: dict) -> Dims:
    assert c.get("sliding_window") is None, "full attention expected"
    return Dims(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab=c["vocab_size"], qkv_bias=False,
        rope_theta=float(c["rope_theta"]), norm_eps=c["rms_norm_eps"],
        max_ctx=c["max_position_embeddings"])


FAMILIES = {"chatglm": _chatglm, "mistral": _mistral}


def dims(config: dict) -> Dims:
    mt = config.get("model_type")
    if mt not in FAMILIES:
        raise KeyError(f"model_type {mt!r} has no reader; known: "
                       f"{sorted(FAMILIES)}")
    return FAMILIES[mt](config)

