"""Ahead-of-time compiles of the served path for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip would refuse —
block shapes off the (8, 128) tiling, ops Mosaic cannot lower, programs
that overflow device memory. Shapes are those of ``chip_smoke.py``:
llama3.2-1b decode (B=16, H=32, KV=8, D=64, page 16, bf16), the map
geometry ``KVPageManager`` derives for 16 slots x 256 pages, and a
1024-token prefill. Every test checks that a Pallas kernel
(``tpu_custom_call``) is in the compiled program, so a reference
lowering cannot pass for it.

The topology is described inside a fixture, never while a module is
imported: only one process may hold the TPU library, and a test run
with several workers would otherwise collect different tests in each.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

B, H, KV, D, PAGE = 16, 32, 8, 64, 16     # llama3.2-1b decode widths
N_SLOTS, MAX_PAGES = 16, 256              # chip_smoke: 4096-token context
HBM_BYTES = 16 << 30                      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def pallas_impl(monkeypatch):
    """Whole programs pick their kernels with ``jax.default_backend()``,
    which is this process's CPU: steer that choice to Pallas."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_default_impl",
                        lambda impl: "pallas" if impl in (None, "auto")
                        else impl)


def _sds(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _translate_args(S, lead=()):
    from repro.paging.kv_manager import _geometry
    g = _geometry(N_SLOTS, MAX_PAGES)
    assert (g.cmt_sets, g.cmt_ways, g.cmt_entries) == (64, 4, 8)
    sw = (g.cmt_sets, g.cmt_ways)
    return g, (S(lead + sw, jnp.int32), S(lead + sw, bool),
               S(lead + sw, bool), S(lead + sw + (g.cmt_entries,), jnp.int32),
               S(lead + (g.n_tvpns * g.entries_per_tp,), jnp.int32),
               S(lead + (N_SLOTS,), jnp.int32), S(lead + (N_SLOTS,), bool))


def test_paged_attention_compiles_at_llama_decode_widths(one_chip):
    from repro.kernels import paged_attention as pa
    S = _sds(one_chip)
    nb = N_SLOTS * MAX_PAGES + 1
    _compile(lambda q, k, v, t, c: pa.paged_attention(
        q, k, v, t, c, return_stats=True),
        S((B, H, D), jnp.bfloat16), S((nb, PAGE, KV * D), jnp.bfloat16),
        S((nb, PAGE, KV * D), jnp.bfloat16), S((B, 128), jnp.int32),
        S((B,), jnp.int32))


@pytest.mark.parametrize("b,kv,n_layers", [(32, 8, 8), (16, 2, 20)],
                         ids=["mistral-7b", "glm4-9b"])
def test_paged_attention_compiles_on_the_stacked_pool(one_chip, b, kv,
                                                      n_layers):
    """The kernel on the stack's bf16 pool [L, NB, P, KV*D] at the chip
    benchmark's decode widths (H=32, D=128, page 16, a 256-page bucket)
    with a traced layer index: Mosaic takes the per-page copies into
    the block buffers, and the stack stays in place — no op of the
    compiled module copies or slices it, or one layer of it."""
    from repro.kernels import paged_attention as pa
    S = _sds(one_chip)
    h, d, maxp, bf16 = 32, 128, 256, jnp.bfloat16
    pool = (n_layers, b * maxp + 1, PAGE, kv * d)
    compiled = _compile(lambda q, k, v, t, c, li: pa.paged_attention(
        q, k, v, t, c, layer=li, return_stats=True),
        S((b, h, d), bf16), S(pool, bf16), S(pool, bf16),
        S((b, maxp), jnp.int32), S((b,), jnp.int32), S((), jnp.int32))
    assert _pool_moves(compiled.as_text(), [pool, pool[1:]]) == []


def test_fmmu_translate_compiles_at_serving_geometry(one_chip):
    from repro.kernels import fmmu_translate as ft
    g, args = _translate_args(_sds(one_chip))
    _compile(lambda *a: ft.fmmu_translate(
        *a, entries_per_block=g.cmt_entries), *args)


def test_fmmu_translate_compiles_under_channel_vmap(one_chip):
    """channels > 1 on one device runs the map as a vmap over a leading
    channel axis; the kernel's blocks must stay legal under it."""
    from repro.kernels import fmmu_translate as ft
    g, args = _translate_args(_sds(one_chip), lead=(4,))
    _compile(jax.vmap(lambda *a: ft.fmmu_translate(
        *a, entries_per_block=g.cmt_entries)), *args)


def test_flash_attention_prefill_compiles_at_1024_tokens(one_chip):
    from repro.kernels import flash_attention as fa
    S = _sds(one_chip)
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
             S((1, 1024, H, D), jnp.bfloat16),
             S((1, 1024, KV, D), jnp.bfloat16),
             S((1, 1024, KV, D), jnp.bfloat16))


def test_ssm_step_compiles_at_granite_widths(one_chip):
    """The Mamba-2 decode state kernel on granite-4.0-h-small's carried
    stack (9 Mamba layers x 64 lanes x 128 heads x 64 x 128, f32),
    updated in place: the stack is aliased, and nothing is copied."""
    from repro.kernels import ssm_step
    S = _sds(one_chip)
    compiled = jax.jit(lambda st, x, dt, a, b, c, d, ly: ssm_step.ssm_step(
        st, x, dt, a, b, c, d, layer=ly), donate_argnums=(0,)).lower(
        S((9, 64, 128, 64, 128), jnp.float32),
        S((64, 128, 64), jnp.bfloat16), S((64, 128), jnp.float32),
        S((128,), jnp.float32), S((64, 128), jnp.bfloat16),
        S((64, 128), jnp.bfloat16), S((128,), jnp.float32),
        S((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 9 * 64 * 128 * 64 * 128 * 4
    assert mem.temp_size_in_bytes < 64 << 20, mem.temp_size_in_bytes


def test_mamba_scan_compiles_at_granite_prefill(one_chip):
    """The chunked Mamba-2 scan over a 2048-token prompt at
    granite-4.0-h-small's heads (128 x 64, d_state 128, chunk 256)."""
    from repro.kernels import mamba_scan as ms
    S = _sds(one_chip)
    _compile(lambda x, dt, a, b, c, d: ms.mamba_chunk_scan(
        x, dt, a, b, c, d, chunk=256),
        S((1, 2048, 128, 64), jnp.bfloat16), S((1, 2048, 128), jnp.float32),
        S((128,), jnp.float32), S((1, 2048, 128), jnp.bfloat16),
        S((1, 2048, 128), jnp.bfloat16), S((128,), jnp.float32))


@pytest.mark.parametrize("k,n", [(4096, 768), (768, 4096)])
def test_grouped_matmul_compiles_at_granite_decode(one_chip, k, n):
    """The expert grouped matmul (megablox gmm) of one decode step:
    64 lanes x top-10 rows over 18 held experts, up/gate and down."""
    from repro.kernels import ops
    S = _sds(one_chip)
    _compile(lambda lhs, rhs, sizes: ops.grouped_matmul(
        lhs, rhs, sizes, impl="pallas"),
        S((640, k), jnp.bfloat16), S((18, k, n), jnp.bfloat16),
        S((18,), jnp.int32))


@pytest.mark.parametrize("call", ["translate", "retranslate"])
def test_sharded_map_translate_compiles_on_four_chips(topo, no_cache,
                                                     pallas_impl, call):
    """The channel-sharded map on a mesh, one channel per chip of a 2x2
    host: the fused translate and the full-map retranslation must both
    hand the kernel its shard (a Pallas kernel cannot be partitioned
    automatically)."""
    from repro.core.fmmu import batch as fb
    from repro.paging.kv_manager import KVPageManager, _geometry
    from repro.parallel.sharding import shard_map
    C = 4
    mesh = Mesh(np.array(topo.devices[:C]), ("channel",))
    g = _geometry(N_SLOTS, MAX_PAGES, C)
    st = jax.eval_shape(lambda: fb.init_sharded_state(
        g, C, N_SLOTS * MAX_PAGES, 1024, n_lanes=N_SLOTS))
    chs = NamedSharding(mesh, P("channel"))
    st = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                     sharding=chs), st)
    if call == "retranslate":
        _compile(lambda fm: KVPageManager._retranslate_sharded(
            g, C, N_SLOTS, MAX_PAGES, mesh, fm), st.fmmu)
        return
    lanes = [_sds(NamedSharding(mesh, P()))((N_SLOTS,), jnp.int32)] * 4
    _compile(shard_map(fb.make_sharded_shard_body(g, C), mesh=mesh,
                       in_specs=(P("channel"), P(), P(), P(), P()),
                       out_specs=(P("channel"), P(), P())), st, *lanes)


# ops that move a whole pool or a whole layer of it (by opcode, or by the
# name XLA gives the fusion around one)
_POOL_MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")
_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.*?) ([a-z][a-z0-9-]*)\(")


def _pool_moves(text, dims):
    """Instructions of the compiled ``text`` that copy or slice a result
    shaped like one of ``dims`` (the stacked pool, as the callers see
    it, and one layer's pool): (name, opcode, type) each."""
    shaped = re.compile(r"\[(%s)\]" % "|".join(
        re.escape(",".join(map(str, d))) for d in dims))
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m or not shaped.search(m.group(2)):
            continue
        name, opcode = m.group(1), m.group(3)
        if opcode in _POOL_MOVES or any(
                part in _POOL_MOVES for part in re.split(r"[_.]", name)):
            found.append((name, opcode, m.group(2)))
    return found


def _macro_step_checks(topo, one_chip, monkeypatch, variant):
    """Compile one whole K=8 macro step (``variant`` "simple" or "full")
    of ServeEngine at llama3.2-1b full width with bf16 weights and a
    16 x 4096-token bf16 KV pool, and check it: both kernels compiled
    in, and parameters + pool + temporaries within one chip's HBM. (The
    KV pool is lane-dense: a [.., KV, D] pool padded to 128 lanes plus
    layout copies needed more than 16 GiB.) The pool rides the layer
    scan's carry and each token is scattered in place, so the step
    neither copies nor slices the pool or one layer of it, and its
    temporaries hold less than one layer's K+V pool (a scan that copied
    the pool held a whole extra pool)."""
    from repro.configs import get_arch
    from repro.models import Runtime, build_model, transformer
    from repro.parallel.sharding import ParallelCtx
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServeEngine
    # keep the pool as shapes: nothing is allocated
    init_caches = transformer.init_decode_caches
    monkeypatch.setattr(transformer, "init_decode_caches",
                        lambda *a, **k: jax.eval_shape(
                            lambda: init_caches(*a, **k)))
    ctx = ParallelCtx(mesh=Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                                ("data", "model")))
    model = build_model(get_arch("llama3.2-1b"),
                        Runtime(compute_dtype=jnp.bfloat16,
                                param_dtype=jnp.bfloat16, page_size=PAGE),
                        ctx)
    eng = ServeEngine(model, None, config=ServeConfig(
        n_slots=N_SLOTS, max_ctx=MAX_PAGES * PAGE, macro_k=8))
    S = _sds(one_chip)
    shapes = lambda tree: jax.tree.map(lambda x: S(x.shape, x.dtype), tree)
    K, i32 = eng.macro_k, jnp.int32
    if variant == "simple":
        fn = eng._macro_simple
        sched = (S((K, N_SLOTS), bool), S((K,), bool), S((K, N_SLOTS), i32))
    else:
        fn, sched = eng._macro, S((N_SLOTS,), i32)
    compiled = fn.lower(
        shapes(jax.eval_shape(model.init, jax.random.key(0))),
        shapes(eng.kvm.state), shapes(eng.caches), S((N_SLOTS,), i32),
        S((N_SLOTS,), i32), sched, S((N_SLOTS,), bool),
        S((N_SLOTS,), i32), None, None, 128).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used
    pool = eng.caches["pool_k"]
    assert pool.shape == (16, 1, N_SLOTS * MAX_PAGES + 1, PAGE, KV * D)
    n_per, n_attn, *layer = pool.shape
    assert _pool_moves(text, [pool.shape, (n_per * n_attn, *layer),
                              layer]) == []
    layer_kv_bytes = 2 * math.prod(pool.shape[2:]) * pool.dtype.itemsize
    assert mem.temp_size_in_bytes < layer_kv_bytes, mem.temp_size_in_bytes


def test_macro_step_fits_one_chip(topo, one_chip, pallas_impl,
                                  monkeypatch):
    """The ``simple`` scan (no lane can finish mid-scan), the steady
    state of every benchmark cell: see _macro_step_checks."""
    _macro_step_checks(topo, one_chip, monkeypatch, "simple")


def test_full_macro_step_fits_one_chip(topo, one_chip, pallas_impl,
                                       monkeypatch):
    """The ``full`` scan (EOS / budget retirement inside the scan): see
    _macro_step_checks."""
    _macro_step_checks(topo, one_chip, monkeypatch, "full")
