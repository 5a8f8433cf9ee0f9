"""Multi-device semantics via subprocesses with 8 virtual CPU devices
(tests otherwise see 1 device; the dry-run owns the 512-device config).

Covers: sharded train step == single-device math, MoE expert parallelism
across the model axis, elastic checkpoint restore 8 -> 4 devices,
compressed-psum correctness, sequence-parallel paged-attention combine.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 900) -> dict:
    """Run `body` in a subprocess with N virtual devices; the body must
    print a final JSON line."""
    prog = textwrap.dedent(f"""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    assert jax.device_count() == {devices}
    """) + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                          text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, f"subprocess failed:\n{proc.stderr[-3000:]}"
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sharded_train_step_matches_single_device():
    out = run_sub("""
    from repro.configs import get_arch, smoke_config
    from repro.models import Runtime, build_model
    from repro.parallel.sharding import ParallelCtx, make_mesh
    from repro.parallel import trivial_ctx
    from repro.data.pipeline import DataConfig, make_batch

    cfg = smoke_config(get_arch("llama3.2-1b"))
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 remat="none")
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                      global_batch=8, pack=False)
    batch = {k: jnp.asarray(v) for k, v in make_batch(dcfg, 0).items()}

    m1 = build_model(cfg, rt, trivial_ctx())
    p = m1.init(jax.random.key(0))
    l1, _ = jax.jit(m1.loss_fn)(p, batch)

    ctx = ParallelCtx(mesh=make_mesh((4, 2), ("data", "model")))
    m2 = build_model(cfg, rt, ctx)
    ps = jax.device_put(p, m2.param_shardings(p))
    bs = jax.device_put(batch, {k: ctx.sharding(P("data"), v.shape[:1])
                                for k, v in batch.items()})
    with ctx.mesh:
        l2, _ = jax.jit(m2.loss_fn)(ps, bs)
    print(json.dumps({"l1": float(l1), "l2": float(l2)}))
    """)
    assert abs(out["l1"] - out["l2"]) < 2e-4, out


def test_moe_expert_parallel_matches_dense():
    out = run_sub("""
    from repro.configs import get_arch, smoke_config
    from repro.models import Runtime
    from repro.models import moe as moe_mod
    from repro.parallel.sharding import ParallelCtx, make_mesh

    cfg = smoke_config(get_arch("dbrx-132b"))
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 capacity_factor=100.0)
    params = moe_mod.init_moe(jax.random.key(0), cfg, jnp.float32)
    x = 0.5 * jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model))
    ctx = ParallelCtx(mesh=make_mesh((2, 4), ("data", "model")))
    with ctx.mesh:
        out, aux = jax.jit(
            lambda p, xx: moe_mod.apply_moe(p, xx, cfg, rt, ctx))(params, x)
    ref = moe_mod.apply_moe_dense_ref(params, x, cfg, rt)
    print(json.dumps({"err": float(jnp.abs(out - ref).max())}))
    """)
    assert out["err"] < 1e-4, out


def test_elastic_restore_8_to_4_devices(tmp_path):
    d = str(tmp_path)
    out = run_sub(f"""
    from repro.training.checkpoint import CheckpointManager
    from repro.parallel.sharding import ParallelCtx, make_mesh
    tree = {{"w": jnp.arange(64.0).reshape(8, 8),
             "m": jnp.arange(32.0).reshape(4, 8)}}
    specs = {{"w": P("data", "model"), "m": P(None, "model")}}
    ctx = ParallelCtx(mesh=make_mesh((4, 2), ("data", "model")))
    sharded = jax.device_put(
        tree, ctx.tree_shardings(specs, tree))
    mgr = CheckpointManager({d!r})
    mgr.save(1, sharded, specs)
    print(json.dumps({{"saved": True}}))
    """, devices=8)
    assert out["saved"]
    out2 = run_sub(f"""
    from repro.training.checkpoint import CheckpointManager
    from repro.training.elastic import make_ctx
    tree_like = {{"w": jnp.zeros((8, 8)), "m": jnp.zeros((4, 8))}}
    ctx = make_ctx(4, model_parallel=2)       # "lost" half the fleet
    mgr = CheckpointManager({d!r})
    got, step = mgr.restore(tree_like, ctx=ctx)
    ok = bool((np.asarray(got["w"]) == np.arange(64.0).reshape(8, 8)).all())
    shard_shape = got["w"].sharding.shard_shape(got["w"].shape)
    print(json.dumps({{"ok": ok, "step": step,
                       "shard_shape": list(shard_shape)}}))
    """, devices=4)
    assert out2["ok"] and out2["step"] == 1
    assert out2["shard_shape"] == [4, 4]   # 2x2 mesh now


def test_compressed_psum_error_feedback():
    out = run_sub("""
    from repro.parallel.collectives import (compressed_psum,
                                            init_error_feedback)
    from repro.parallel.sharding import make_mesh
    mesh = make_mesh((4,), ("pod",))
    g = jax.random.normal(jax.random.key(0), (4, 256))

    def body(gg, ee):
        return compressed_psum(gg, "pod", ee)

    from repro.parallel.sharding import shard_map
    fn = shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")),
                   out_specs=(P(None), P("pod")), check_vma=False)
    err = jnp.zeros((4, 256))
    # shard_map with in_specs P('pod') splits axis 0: each shard [1,256]
    total, err2 = fn(g, err)
    want = g.sum(axis=0, keepdims=True)
    rel = float(jnp.abs(total[:1] - want).max() / jnp.abs(want).max())
    # with error feedback, two successive reductions of the same gradient
    # have bounded bias: second-round residual grows smaller
    total2, err3 = fn(g, err2)
    r1 = float(jnp.abs(err2).mean())
    print(json.dumps({"rel": rel, "resid": r1}))
    """)
    assert out["rel"] < 0.05, out
    assert out["resid"] < 0.05


def test_sequence_parallel_paged_decode_combine():
    """Pages striped across the data axis; per-shard partial attention +
    cross-shard flash-decoding combine == single-shot attention."""
    out = run_sub("""
    from repro.kernels import ref
    from repro.parallel.sharding import make_mesh
    b, h, d, page, maxp = 2, 4, 16, 8, 8
    nb = b * maxp
    k = jax.random.key(0)
    q = jax.random.normal(jax.random.fold_in(k, 1), (b, h, d))
    kp = jax.random.normal(jax.random.fold_in(k, 2), (nb, page, 2, d))
    vp = jax.random.normal(jax.random.fold_in(k, 3), (nb, page, 2, d))
    table = jnp.arange(nb).reshape(b, maxp)
    ctx = jnp.array([61, 64])
    want = ref.paged_attention_naive(q, kp, vp, table, ctx)

    mesh = make_mesh((4,), ("data",))
    pages_per_shard = maxp // 4

    def shard_fn(q, kp, vp, table, ctxl):
        # table [b, maxp/4] local page ids; ctx clipped to local range
        i = jax.lax.axis_index("data")
        lo = i * pages_per_shard * page
        local_ctx = jnp.clip(ctxl - lo, 0, pages_per_shard * page)
        o, (m, l) = ref.paged_attention_naive(
            q, kp, vp, table, local_ctx, return_stats=True)
        outs = jax.lax.all_gather(o, "data")
        ms = jax.lax.all_gather(m, "data")
        ls = jax.lax.all_gather(l, "data")
        return ref.combine_partial_attention(outs, ms, ls)

    from repro.parallel.sharding import shard_map
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(None), P(None), P(None), P(None, "data"), P(None)),
        out_specs=P(None), check_vma=False)
    got = fn(q, kp, vp, table, ctx)
    print(json.dumps({"err": float(jnp.abs(got - want).max())}))
    """)
    assert out["err"] < 1e-5, out


def test_striped_paged_decode_attention_exact():
    """Runtime.shard_kv_pool_pages: range-partitioned pools + page-mask
    partial attention + flash-decoding combine == plain paged decode."""
    out = run_sub("""
    from repro.configs import get_arch, smoke_config
    from repro.models import Runtime
    from repro.models import attention
    from repro.parallel.sharding import ParallelCtx, make_mesh
    cfg = smoke_config(get_arch('llama3.2-1b'))
    ctx = ParallelCtx(mesh=make_mesh((2, 4), ('data', 'model')))
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 page_size=8)
    params = attention.init_attention(jax.random.key(0), cfg, jnp.float32)
    NB, page, maxp = 64, 8, 8
    pool_k = jax.random.normal(jax.random.key(1),
                               (NB, page, cfg.n_kv_heads * cfg.head_dim))
    pool_v = jax.random.normal(jax.random.key(2),
                               (NB, page, cfg.n_kv_heads * cfg.head_dim))
    errs = {}
    # batch=1: pages striped across every chip, combine over all axes
    table = jax.random.permutation(jax.random.key(3),
                                   jnp.arange(NB))[:maxp].reshape(1, maxp)
    ctxl = jnp.array([13])
    x = 0.1 * jax.random.normal(jax.random.key(4), (1, cfg.d_model))
    with ctx.mesh:
        a1, b1, c1 = jax.jit(lambda: attention.attn_decode_paged(
            params, x, cfg, rt, pool_k=pool_k, pool_v=pool_v,
            block_table=table, ctx_lens=ctxl))()
        a2, b2, c2 = jax.jit(lambda: attention.attn_decode_paged_striped(
            params, x, cfg, rt, ctx, pool_k=pool_k, pool_v=pool_v,
            block_table=table, ctx_lens=ctxl))()
    errs['b1_y'] = float(jnp.abs(a1 - a2).max())
    errs['b1_pool'] = float(jnp.abs(b1 - b2).max())
    # batch=4: data-local allocation, combine over model only
    t0 = jax.random.permutation(jax.random.key(6), jnp.arange(32))[:16]
    t1 = 32 + jax.random.permutation(jax.random.key(7), jnp.arange(32))[:16]
    tb = jnp.concatenate([t0.reshape(2, 8), t1.reshape(2, 8)])
    cl = jnp.array([13, 30, 47, 62])
    xb = 0.1 * jax.random.normal(jax.random.key(8), (4, cfg.d_model))
    with ctx.mesh:
        a1, b1, c1 = jax.jit(lambda: attention.attn_decode_paged(
            params, xb, cfg, rt, pool_k=pool_k, pool_v=pool_v,
            block_table=tb, ctx_lens=cl))()
        a2, b2, c2 = jax.jit(lambda: attention.attn_decode_paged_striped(
            params, xb, cfg, rt, ctx, pool_k=pool_k, pool_v=pool_v,
            block_table=tb, ctx_lens=cl))()
    errs['b4_y'] = float(jnp.abs(a1 - a2).max())
    errs['b4_pool'] = float(jnp.abs(b1 - b2).max())
    print(json.dumps(errs))
    """)
    assert all(v < 1e-5 for v in out.values()), out
