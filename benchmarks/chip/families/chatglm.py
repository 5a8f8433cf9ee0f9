"""GLM-4 in THUDM's ChatGLM naming: the dense decoder of dense.py."""
from __future__ import annotations

from dense import *  # noqa: F401,F403  (the family interface)
from dims import Dims


def dims(c: dict) -> Dims:
    # the rope base is 10000 * rope_ratio
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
