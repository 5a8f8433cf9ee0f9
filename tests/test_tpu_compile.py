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
import os

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


def test_macro_step_fits_one_chip(topo, one_chip, pallas_impl,
                                  monkeypatch):
    """One whole K=8 macro step of ServeEngine at llama3.2-1b full width
    with bf16 weights and a 16 x 4096-token bf16 KV pool: both kernels
    compiled in, and parameters + pool + temporaries within one chip's
    HBM. (The KV pool is lane-dense: a [.., KV, D] pool padded to
    128 lanes plus layout copies needed more than 16 GiB.)"""
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
    sched = (S((K, N_SLOTS), bool), S((K,), bool), S((K, N_SLOTS), i32))
    compiled = eng._macro_simple.lower(
        shapes(jax.eval_shape(model.init, jax.random.key(0))),
        shapes(eng.kvm.state), shapes(eng.caches), S((N_SLOTS,), i32),
        S((N_SLOTS,), i32), sched, S((N_SLOTS,), bool),
        S((N_SLOTS,), i32), None, None, 128).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, used
