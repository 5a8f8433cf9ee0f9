#!/usr/bin/env python3
"""Smoke run of the served path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py            # one chip: serve llama3.2-1b
    python chip_smoke.py --chips 4  # four chips: the channel-sharded map

With no option it builds llama3.2-1b at its published widths with
random bf16 weights and a bf16 paged KV pool, serves 8 seeded requests
(prompts of 128-1024 tokens, 64 new tokens each) to completion through
``ServeEngine``'s K-step macro path, and checks every generated token
against the model's own float32 dense forward on the same weights.

With ``--chips 4`` it runs only the channel-sharded map: one seeded
admit/extend/precommit/swap/free trace through a
``KVPageManager(channels=4)`` on the 4-chip ``shard_map`` lowering and,
beside it, on the single-device vmap lowering; block tables, page lists,
free lists and pool rows must stay bit-identical.

The script exits non-zero, and prints no result line, when JAX finds
no TPU or any phase fails. Otherwise the last line of standard output
is ``{"ok": true, "device": {"platform", "kind", "count"}}``. Times
printed on earlier lines are smoke timings of one run, not metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# teacher-forced check of the bf16 engine against the f32 forward:
# random-init llama3.2-1b logits have a spread of about 1 (embedding
# std 0.02 x sqrt(d_model 2048)), so bf16 rounding moves them by a few
# hundredths. Every engine token must sit within GAP_TOL of the f32
# maximum, and at least MIN_MATCH of them must be the f32 argmax itself.
GAP_TOL = 0.25
MIN_MATCH = 0.5

# prompt lengths, seeded order: each distinct length is one prefill
# compile, so the range 128-1024 is covered by four lengths
PROMPT_LENS = (128, 256, 512, 1024)


def log(*a):
    print(*a, flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str):
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise SmokeFailure(what)


class CompileClock:
    """Sums backend compile time (persistent-cache reads included) and
    counts persistent-cache hits through JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def dense_logits(model, params, tokens, n_last: int):
    """The model's own dense forward (the prefill / loss_fn path) for
    tokens [1, S]: logits [n_last, V] of the last n_last positions."""
    from repro.models import common, transformer
    x, positions = model._fuse_inputs(params, {"tokens": tokens})
    x, _, _ = transformer.stack_forward(
        params["stack"], x, model.cfg, model.rt, model.ctx,
        positions=positions)
    x = common.rms_norm(x[:, -n_last:], params["final_norm"],
                        model.cfg.norm_eps)
    return model._logits(params, x)[0]


def serve_phase(cfg, *, seed: int, n_slots: int = 16, max_ctx: int = 4096,
                macro_k: int = 8, page: int = 16,
                prompt_lens=PROMPT_LENS, n_requests: int = 8,
                max_new: int = 64):
    """Serve seeded requests through ServeEngine's macro path, then check
    the shortest and the longest request against the f32 forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import Runtime, build_model
    from repro.serving.config import ServeConfig
    from repro.serving.engine import ServeEngine

    rt = Runtime(compute_dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
                 page_size=page)
    model = build_model(cfg, rt)
    params = jax.jit(model.init)(jax.random.key(seed))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"model {cfg.name}: {n_params} params, bf16 weights and KV")
    eng = ServeEngine(model, params, config=ServeConfig(
        n_slots=n_slots, max_ctx=max_ctx, macro_k=macro_k))
    pool = eng.caches["pool_k"]
    log(f"kv pool: 2 x {pool.shape} {pool.dtype}, "
        f"{2 * pool.size * pool.dtype.itemsize} bytes")

    rng = np.random.default_rng(seed)
    lens = rng.permutation(np.repeat(np.asarray(prompt_lens),
                                     -(-n_requests // len(prompt_lens))))
    prompts = {}
    for n in lens[:n_requests]:
        toks = rng.integers(2, cfg.vocab_size, int(n)).tolist()
        prompts[eng.submit(toks, max_new=max_new)] = toks

    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    m = eng.metrics
    log(f"served: {len(done)}/{len(prompts)} requests completed, "
        f"{m['generated']} tokens generated, {m['macro_steps']} macro "
        f"steps, {m['macro_fallbacks']} single-step fallbacks")
    log(f"smoke timing (compile included, not a metric): run {wall:.3f} s")
    log("map hit stats:", json.dumps(eng.kvm.hit_stats().as_dict()))
    check(sorted(done) == sorted(prompts), "not every request completed")
    check(all(len(done[r]) == max_new for r in prompts),
          "a request ended short of max_new")
    check(m["macro_steps"] > 0, "the K-step macro path never ran")

    ref_rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                     kernel_impl="naive", remat="none", page_size=page)
    ref = build_model(cfg, ref_rt)
    params32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    del eng, params
    fwd = jax.jit(lambda p, t: dense_logits(ref, p, t, max_new))
    by_len = sorted(prompts, key=lambda r: len(prompts[r]))
    for rid in dict.fromkeys((by_len[0], by_len[-1])):
        out = done[rid]
        toks = jnp.asarray([prompts[rid] + out[:-1]], jnp.int32)
        logits = np.asarray(fwd(params32, toks))
        picked = logits[np.arange(max_new), out]
        gap = logits.max(axis=1) - picked
        match = float((logits.argmax(axis=1) == np.asarray(out)).mean())
        log(f"request {rid} (prompt {len(prompts[rid])}): max f32 logit "
            f"gap of engine tokens {gap.max():.4f} (tolerance {GAP_TOL}), "
            f"f32 argmax match {match:.3f} (minimum {MIN_MATCH})")
        check(np.isfinite(logits).all(), "non-finite reference logits")
        check(gap.max() <= GAP_TOL and match >= MIN_MATCH,
              f"request {rid}: engine tokens disagree with the f32 forward")


def sharded_map_phase(*, seed: int, channels: int = 4, n_slots: int = 16,
                      max_pages: int = 256, n_host_blocks: int = 1024,
                      steps: int = 96):
    """One seeded map trace on the shard_map lowering (one channel per
    device) and on the vmap lowering, compared bit for bit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.paging.kv_manager import KVPageManager
    from repro.paging.pool import OutOfBlocks

    check(len(jax.devices()) >= channels,
          f"{channels} channels need {channels} devices")
    n_dev = n_slots * max_pages // 2       # half the pages fit on device
    kw = dict(n_slots=n_slots, max_pages=max_pages,
              n_device_blocks=n_dev, n_host_blocks=n_host_blocks,
              channels=channels)
    mesh_kvm = KVPageManager(use_mesh=True, **kw)
    vmap_kvm = KVPageManager(use_mesh=False, **kw)
    check(mesh_kvm.mesh is not None and vmap_kvm.mesh is None,
          "use_mesh did not pick the lowering")
    rows = n_dev + n_host_blocks + 1
    pools = {id(k): jnp.arange(rows * 8, dtype=jnp.int32).reshape(rows, 8)
             for k in (mesh_kvm, vmap_kvm)}
    rng = random.Random(seed)
    live: set = set()
    counts: dict = {}
    sizes = (1, 2, 4, 8, 16)

    def apply(kvm, op, s, n):
        if op == "new":
            kvm.new_seq(s, n)
        elif op == "extend":
            kvm.extend_seq(s, n)
        elif op == "pre":
            kvm.precommit_growth(s)
        elif op == "free":
            kvm.free_seq(s)
        else:
            fn = kvm.swap_out if op == "swap_out" else kvm.swap_in
            [pools[id(kvm)]], _ = fn(s, [pools[id(kvm)]], check=True)

    def same_host_state(step):
        a, b = mesh_kvm, vmap_kvm
        check(a.seq_pages == b.seq_pages, f"step {step}: page lists")
        for c in range(channels):
            check(a.pool._free_dev_ch[c] == b.pool._free_dev_ch[c],
                  f"step {step}: device free list of channel {c}")
            check(a.pool._free_host_ch[c] == b.pool._free_host_ch[c],
                  f"step {step}: host free list of channel {c}")

    def same_device_state(step):
        np.testing.assert_array_equal(
            np.asarray(mesh_kvm.block_tables()),
            np.asarray(vmap_kvm.block_tables()),
            f"step {step}: block tables")
        np.testing.assert_array_equal(
            np.asarray(pools[id(mesh_kvm)]), np.asarray(pools[id(vmap_kvm)]),
            f"step {step}: pool rows")

    t0 = time.perf_counter()
    for step in range(steps):
        ops = ["new"] if len(live) < n_slots else []
        if live:
            ops += ["extend", "pre", "free", "swap_out", "swap_in"]
        op = rng.choice(ops)
        n = rng.choice(sizes)
        if op == "new":
            s = rng.choice([x for x in range(n_slots) if x not in live])
        else:
            s = rng.choice(sorted(live))
            room = max_pages - len(mesh_kvm.seq_pages[s])
            if op == "extend":
                n = min(n, room)
            elif op == "pre":
                s = [x for x in sorted(live) if mesh_kvm.is_resident(x)
                     and len(mesh_kvm.seq_pages[x]) < max_pages]
            if (op == "extend" and not n) or (op == "pre" and not s):
                continue
        errs = []
        for kvm in (mesh_kvm, vmap_kvm):
            try:
                apply(kvm, op, s, n)
                errs.append(None)
            except OutOfBlocks as e:
                errs.append(type(e).__name__)
        check(errs[0] == errs[1], f"step {step}: {op} raised {errs}")
        if errs[0] is None:
            counts[op] = counts.get(op, 0) + 1
            if op == "new":
                live.add(s)
            elif op == "free":
                live.discard(s)
        same_host_state(step)
        if step % 8 == 7:
            same_device_state(step)
    same_device_state(steps)
    np.testing.assert_array_equal(np.asarray(mesh_kvm.block_tables()),
                                  np.asarray(mesh_kvm.retranslate_tables()),
                                  "incremental table vs retranslation")
    for kvm in (mesh_kvm, vmap_kvm):
        kvm.sync_allocator()
    for name in ("free_stack", "free_n", "host_stack", "host_n"):
        np.testing.assert_array_equal(
            np.asarray(getattr(mesh_kvm.state, name)),
            np.asarray(getattr(vmap_kvm.state, name)), name)
    log(f"map trace: {steps} steps, committed ops {json.dumps(counts)}, "
        f"{time.perf_counter() - t0:.3f} s (smoke timing); shard_map and "
        "vmap lowerings bit-identical")
    shards = sorted(mesh_kvm.state.table.addressable_shards,
                    key=lambda sh: sh.index[0].start)
    for sh in shards:
        log(f"channel shard {sh.index[0].start}: {sh.device}")
    check(len({sh.device for sh in shards}) == channels,
          "channel shards share a device")
    check((mesh_kvm.channel_lanes > 0).all(), "a channel never saw a lane")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, {len(devs)} "
              "found", file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    log(f"device: {devs[0].device_kind} x {len(devs)}; compile cache "
        f"{compile_cache.enable()}")
    clock = CompileClock()
    if args.chips == 4:
        sharded_map_phase(seed=args.seed, channels=4)
    else:
        from repro.configs import get_arch
        serve_phase(get_arch("llama3.2-1b"), seed=args.seed)
    log(f"backend compile {clock.secs:.3f} s, {clock.hits} persistent-"
        "cache hits (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
