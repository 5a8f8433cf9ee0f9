"""Model families found by name: every configuration of BENCHMARK.json
resolves to its module under families/, an unknown model_type names the
modules there, and the dense families give, through the lookup, the same
weights and reference readings bit for bit as before they were modules.

The digests were recorded at toy sizes on the CPU from the code before
the move (weights.make(seed, d), reference.Reference(d, seed) over
dims.dims); the hidden states are digested over the sequence's own
rows, which do not depend on how many threads the CPU's matmuls use."""
import dataclasses
import hashlib
import json
import os

import jax
import numpy as np
import pytest

import harness
import reference
import weights

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
SEED = 2**33 + 5

GLM_TOY = {"model_type": "chatglm", "add_bias_linear": False,
           "add_qkv_bias": True, "ffn_hidden_size": 96, "hidden_size": 64,
           "kv_channels": 16, "layernorm_epsilon": 1.5625e-07,
           "multi_query_attention": True, "multi_query_group_num": 2,
           "num_attention_heads": 4, "num_layers": 2,
           "padded_vocab_size": 384, "rmsnorm": True, "seq_length": 128}

GOLDEN = {
    "chatglm": {
        "weights": "e3678d6f27bf43a379b297bf9a08720040bd8214839297bd3d3e3b53a6a115b4",
        "hidden": "168c4a991722a1a20f454c977ee2b5f53f71f310413cda2b11dd12d1d219be5e",
        "hidden_fp8": "c38cc3cb1858487354b57b354ddc0380b721f623c60084a6778ccc5d7bd59bdd",
        "gaps": "02709326c2a7439fd77a2540a7d78bcde75f0fb19bc1bef37235835412d6db5a",
    },
    "mistral": {
        "weights": "fde372f4823e35ef7cf4557a49631aee6eb02e02d837855fb78b9b883105a32e",
        "hidden": "0f4b0ed55c04f373055649de55ae09ada1e52eb35535129fe48dd1304021d2f6",
        "hidden_fp8": "d5cdeac6b19fd6e382a539d3b263d95defa7e122779ff51c955e477bbaff5f7c",
        "gaps": "699068475ace37052245dee3efb4f7867fb3fe88ae5f5edc054eb161310e34a1",
    },
}


def _toy(model_type):
    if model_type == "chatglm":
        return GLM_TOY
    with open(os.path.join(HERE, "tiny", "configs", "tiny.json")) as f:
        return json.load(f)


def _digest(*arrays):
    m = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        m.update(str(a.dtype).encode())
        m.update(str(a.shape).encode())
        m.update(np.ascontiguousarray(a).tobytes())
    return m.hexdigest()


@pytest.fixture(scope="module", params=["chatglm", "mistral"])
def toy(request):
    config = _toy(request.param)
    fam = harness.load_family(config["model_type"])
    return request.param, fam, fam.dims(config)


def test_weights_bit_identical(toy):
    name, fam, d = toy
    leaves = jax.tree.leaves(weights.make(fam, SEED, d))  # sorted keys
    assert _digest(*[np.asarray(x.astype(np.float32)) for x in leaves]) \
        == GOLDEN[name]["weights"]


def test_reference_bit_identical(toy):
    name, fam, d = toy
    toks = np.random.default_rng(3).integers(0, d.vocab, 40).astype(np.int32)
    ref = reference.Reference(fam, d, SEED)
    S = len(toks)
    assert _digest(ref.hidden(toks)[:S]) == GOLDEN[name]["hidden"]
    assert _digest(ref.hidden(toks, "fp8")[:S]) == GOLDEN[name]["hidden_fp8"]
    g = ref.gaps(toks[:24], toks[24:], control=True)
    assert _digest(g["program"], g["fp8"]) == GOLDEN[name]["gaps"]


def test_every_configuration_resolves():
    """Each configuration's family reads its file, and the weights it
    makes fit the program's parameter tree at the configuration's own
    sizes (shapes only: nothing is allocated)."""
    from repro.models import Runtime, build_model

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        fam = harness.load_family(config["model_type"])
        d = fam.dims(config)
        assert fam.layer_kinds(d) and len(fam.layer_kinds(d)) == d.n_layers
        model = build_model(fam.arch(d, entry["name"]), Runtime(
            param_dtype=jax.numpy.bfloat16))
        want = model.param_shapes()
        got = jax.eval_shape(
            lambda: fam.to_program(weights.make(fam, 1, d), d))
        assert jax.tree.structure(got) == jax.tree.structure(want)
        assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
            [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def test_unknown_model_type_names_the_family_files():
    found = [f[:-3] for f in os.listdir(os.path.join(BENCH, "families"))
             if f.endswith(".py")]
    assert {"chatglm", "mistral"} <= set(found)
    with pytest.raises(harness.BenchError) as e:
        harness.load_family("no_such_family")
    for name in found + ["no_such_family"]:
        assert repr(name) in str(e.value)


def test_cell_on_more_chips_is_refused():
    """The engine is built on one chip; a cell that asks for more is
    refused before anything is built, not run on one of them."""
    cell = harness.load_cell("mistral-7b.decode_long")
    with pytest.raises(harness.BenchError, match="4 chips"):
        harness.build_engine(dataclasses.replace(cell, chips=4), SEED)
