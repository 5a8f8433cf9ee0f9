"""granitemoehybrid (IBM Granite 4.0-H: Mamba-2 and NoPE attention, a
routed MoE of which this chip holds a share, a shared expert, scalar
multipliers) on the served path, at toy widths on the CPU.

The reference is the chip benchmark's own, loaded by path from
benchmarks/chip/families/granitemoehybrid.py, so there is one: float32,
every contraction at HIGHEST precision, no kernels, no cache. The toy
configuration is the benchmark's CPU toy (tests/tiny/configs/
tiny_granite.json): two periods of a Mamba-2 and an attention layer, 8
experts of which 2 are held, top-2, a shared expert, embedding x12,
residual x0.22, attention 1/16, logits /16. The program runs in float32
here, on the same bf16-valued weights, so it and the reference differ
only in the order of float32 sums."""
import functools
import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig, MoEConfig
from repro.models import Runtime, build_model, mlp, moe, transformer
from repro.parallel.sharding import trivial_ctx
from repro.serving.config import ServeConfig
from repro.serving.engine import ServeEngine, _scatter_prefill

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
TOY = os.path.join(BENCH, "tests", "tiny", "configs", "tiny_granite.json")
SEED = 2**33 + 3
F32 = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.bfloat16,
              remat="none", page_size=8)


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    """The benchmark's modules: the family, its weights and the shared
    reference runner (the family imports them by their bare names)."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import reference
    import weights
    fam = _load("family_granitemoehybrid",
                os.path.join(BENCH, "families", "granitemoehybrid.py"))
    with open(TOY) as f:
        d = fam.dims(json.load(f))
    return fam, d, reference, weights


@pytest.fixture(scope="module")
def served(bench):
    fam, d, reference, weights = bench
    model = build_model(fam.arch(d, "tiny_granite"), F32)
    params = fam.to_program(weights.make(fam, SEED, d), d)
    ref = reference.Reference(fam, d, SEED, q_block=16, min_len=64)
    return model, params, ref


def _ref_logits(fam, d, ref, seq):
    return np.asarray(fam.head(ref.hidden(np.asarray(seq)), ref.outer,
                               False, d))[:len(seq)]


def test_prefill_then_decode_matches_reference(bench, served):
    """The program's prefill, then decode one token at a time through the
    paged KV pool and the carried Mamba states (the engine's scatter of
    the prefill caches), against the reference's full forward pass at
    every position. Tolerance 2e-6 on logits whose spread is ~1e-3 (the
    head is the embedding, divided by 16): float32 on both sides, the
    sums in another order; 1e-3 of the spread."""
    fam, d, _, _ = bench
    model, params, ref = served
    cfg, L, n_new = model.cfg, 32, 6
    prompt = np.random.default_rng(0).integers(0, d.vocab, L)
    logits, cols = jax.jit(model.prefill)(params,
                                          {"tokens": jnp.asarray(prompt)[None]})
    max_pages = d.max_ctx // F32.page_size
    caches = transformer.init_decode_caches(cfg, F32, 1, max_pages,
                                            max_pages + 1, jnp.float32)
    table = jnp.arange(max_pages, dtype=jnp.int32)[None]
    caches = _scatter_prefill(cfg, F32, caches, cols, table[0], 0)
    step = jax.jit(model.decode_step)
    got, seq = [np.asarray(logits[0])], list(prompt)
    for t in range(n_new - 1):
        seq.append(int(np.argmax(got[-1])))
        logits, caches = step(params, jnp.asarray(seq[-1:], jnp.int32),
                              caches, ctx_lens=jnp.asarray([L + t], jnp.int32),
                              block_table=table)
        got.append(np.asarray(logits[0]))
    want = _ref_logits(fam, d, ref, seq)[L - 1:]
    assert want.std() > 5e-4            # the logits are not degenerate
    np.testing.assert_allclose(np.stack(got), want, rtol=0, atol=2e-6)


def test_engine_serves_reference_greedy_tokens(bench, served):
    """ServeEngine itself (scheduler, map, K-step scans over two lanes)
    serves, at each position, the token the reference puts first, up to
    ties: each served token's reference logit lies within 2e-6 (the
    tolerance above) of the reference's best."""
    fam, d, _, _ = bench
    model, params, ref = served
    eng = ServeEngine(model, params, config=ServeConfig(
        n_slots=2, max_ctx=d.max_ctx, macro_k=4, channels=1))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, d.vocab, n).tolist() for n in (16, 32)]
    rids = [eng.submit(p, max_new=12) for p in prompts]
    done = eng.run()
    for rid, p in zip(rids, prompts):
        out = done[rid]
        assert len(out) == 12
        lg = _ref_logits(fam, d, ref, p + out[:-1])[len(p) - 1:]
        gap = lg.max(-1) - lg[np.arange(len(out)), out]
        assert gap.max() <= 2e-6, gap


def _moe_cfg(n_held=0, held_offset=0, shared=64):
    return ArchConfig(name="moe", family="moe", n_layers=1, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=0, vocab_size=64,
                      moe=MoEConfig(n_experts=8, top_k=2, d_ff=16,
                                    n_held=n_held, held_offset=held_offset,
                                    shared_d_ff=shared))


def _dropless(params, x, cfg):
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32)
    out, aux = moe.apply_moe(params, x, cfg, rt, trivial_ctx(),
                             dropless=True)
    assert float(aux) == 0.0
    return out


def test_expert_shares_sum_to_the_uncut_layer():
    """Four chips' shares of a layer (2 of its 8 experts each, as the
    benchmark's 18 of 72 over four), each routing over all 8 and
    computing its own experts' part, add up to the uncut layer, the
    shared expert counted once. float32, sums in another order: 1e-5."""
    full_cfg = _moe_cfg()
    params = moe.init_moe(jax.random.key(0), full_cfg, jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 24, 32))
    whole = _dropless(params, x, full_cfg)
    parts = 0.0
    for off in range(0, 8, 2):
        cfg = _moe_cfg(n_held=2, held_offset=off, shared=0)
        share = {"router": params["router"],
                 **{k: params[k][off:off + 2] for k in ("wg", "wu", "wd")}}
        parts = parts + _dropless(share, x, cfg)
    shared = mlp.apply_mlp(params["shared"], x, full_cfg, F32)
    np.testing.assert_allclose(parts + shared, whole, atol=1e-5, rtol=1e-5)
    assert not np.allclose(parts, whole, atol=1e-3)   # the shared expert


def test_adversarial_routing_drops_nothing():
    """Every token routed to the same two experts (both held here): the
    serving path equals the dense reference, which drops nothing, while
    the static-capacity training path at capacity factor 1 drops most
    of them. float32: 1e-5."""
    cfg = _moe_cfg(n_held=2, held_offset=0)
    params = moe.init_moe(jax.random.key(2), cfg, jnp.float32)
    params["router"] = jnp.zeros((32, 8)).at[:, :2].set(1.0)
    x = jnp.abs(jax.random.normal(jax.random.key(3), (2, 24, 32)))
    got = _dropless(params, x, cfg)
    want = moe.apply_moe_dense_ref(params, x, cfg, F32)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 capacity_factor=1.0)
    dropped, _ = moe.apply_moe(params, x, cfg, rt, trivial_ctx())
    assert not np.allclose(dropped, want, atol=1e-3)


# sha256 of the jaxpr of one K=4 ``simple`` decode scan, recorded on the
# code before the multipliers, the attention scale and the dropless MoE
# existed (same toy shapes, CPU lowering)
DENSE_SCAN_JAXPR = {
    "chatglm": "8279bff51ee8db5deb7fc96c74f191aec693f35352c32868b029d78dd391778e",
    "mistral": "1eefdf0d7de5c475a16b1a28b5cf953a0a6e230c92646c170a25aa0c6380fbe1",
}
GLM_TOY = {"model_type": "chatglm", "add_bias_linear": False,
           "add_qkv_bias": True, "ffn_hidden_size": 96, "hidden_size": 64,
           "kv_channels": 16, "layernorm_epsilon": 1.5625e-07,
           "multi_query_attention": True, "multi_query_group_num": 2,
           "num_attention_heads": 4, "num_layers": 2,
           "padded_vocab_size": 384, "rmsnorm": True, "seq_length": 128}


@pytest.mark.parametrize("model_type", sorted(DENSE_SCAN_JAXPR))
def test_dense_decode_scan_jaxpr_unchanged(bench, model_type):
    """At the default multipliers and attention scale, the benchmark's
    dense families (GLM-4, Mistral) trace the same decode scan as
    before these existed."""
    import harness
    if model_type == "chatglm":
        c = GLM_TOY
    else:
        with open(os.path.join(BENCH, "tests", "tiny", "configs",
                               "tiny.json")) as f:
            c = json.load(f)
    fam = harness.load_family(model_type)
    d = fam.dims(c)
    model = build_model(fam.arch(d, "toy"), Runtime(
        compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, page_size=8))
    eng = ServeEngine(model, None, config=ServeConfig(
        n_slots=4, max_ctx=d.max_ctx, macro_k=4, channels=1))
    S, i32, K, N = jax.ShapeDtypeStruct, jnp.int32, 4, 4
    sh = lambda t: jax.tree.map(lambda x: S(x.shape, x.dtype), t)
    jx = jax.make_jaxpr(functools.partial(eng._macro_fn, simple=True),
                        static_argnums=(10,))(
        sh(model.param_shapes()), sh(eng.kvm.state), sh(eng.caches),
        S((N,), i32), S((N,), i32),
        (S((K, N), bool), S((K,), bool), S((K, N), i32)),
        S((N,), bool), S((N,), i32), None, None, eng.max_pages)
    assert hashlib.sha256(str(jx).encode()).hexdigest() == \
        DENSE_SCAN_JAXPR[model_type]
