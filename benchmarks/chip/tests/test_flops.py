"""FLOP and byte counts against hand-computed values for both served
configurations, reached through their family modules (the counts that
Driver._book adds) and flops.py (the dense terms they sum)."""
import json
import os

import pytest

import flops
import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _family(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        config = json.load(f)
    fam = harness.load_family(config["model_type"])
    return fam, fam.dims(config)


def test_glm_sizes_and_counts():
    _, d = _family("glm4-9b")
    assert (d.n_layers, d.d_model, d.n_heads, d.n_kv_heads, d.head_dim,
            d.d_ff, d.vocab, d.qkv_bias) == (20, 4096, 32, 2, 128, 13696,
                                             151552, True)
    # q 4096*4096, k/v 2 * 4096*256, o 4096*4096, mlp 3 * 4096*13696,
    # qkv bias 4096+2*256, two norms 2*4096
    assert flops.layer_weights(d) == (16777216 + 2097152 + 16777216
                                      + 168296448 + 4608 + 8192)
    per_layer = 2 * (16777216 + 2097152 + 16777216 + 168296448)
    assert flops.matmul_flops_per_token(d) == 20 * per_layer
    assert flops.head_flops(d) == 2 * 4096 * 151552
    # one decode token at 1000 keys: + 20 layers * 4 * 32 * 128 * 1000
    assert flops.decode_flops(d, 1000) == (20 * per_layer + 327680000
                                           + 1241513984)
    # live KV of 1000 keys: 20 layers * (K and V) * 1000 * 256 * 2 B
    # plus q and out 2 * 4096 * 2 B per layer
    assert flops.paged_attn_bytes(d, 1000) == 20 * (2 * 1000 * 256 * 2
                                                   + 2 * 4096 * 2)


def test_mistral_sizes_and_counts():
    fam, d = _family("mistral-7b")
    assert (d.n_layers, d.d_model, d.n_kv_heads, d.d_ff, d.vocab,
            d.qkv_bias, d.rope_theta) == (8, 4096, 8, 14336, 32000, False,
                                          1e6)
    per_layer = 2 * (4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096
                     + 3 * 4096 * 14336)
    assert flops.matmul_flops_per_token(d) == 8 * per_layer
    # prefill of 4 tokens: 4 tokens of matmuls, causal attention over
    # 1+2+3+4 = 10 keys, one head row
    assert fam.prefill_flops(d, 4) == (4 * 8 * per_layer
                                       + 8 * 4 * 32 * 128 * 10
                                       + 2 * 4096 * 32000)
    assert flops.paged_attn_bytes(d, 2048) == 8 * (2 * 2048 * 1024 * 2
                                                   + 2 * 4096 * 2)


@pytest.mark.parametrize("name", ["glm4-9b", "mistral-7b"])
def test_runs_are_sums_of_tokens(name):
    fam, d = _family(name)
    first, n = 777, 19
    assert fam.decode_run_flops(d, first, n) == sum(
        flops.decode_flops(d, first + i) for i in range(n))
    f, b = fam.paged_attn_run(d, first, n)
    assert b == sum(flops.paged_attn_bytes(d, first + i) for i in range(n))
    assert f == sum(flops.attn_flops(d, first + i) for i in range(n))
    assert fam.decode_run_flops(d, first, 0) == 0
