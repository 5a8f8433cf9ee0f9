"""Serving launcher: FMMU-paged continuous-batching demo.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \\
      --requests 6 --max-new 16
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, smoke_config
from repro.launch import compile_cache
from repro.models import Runtime, build_model
from repro.serving.engine import ServeEngine


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-ctx", type=int, default=256)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--host-blocks", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    rt = Runtime(compute_dtype=jnp.float32, param_dtype=jnp.float32,
                 remat="none", page_size=args.page_size,
                 capacity_factor=100.0)
    model = build_model(cfg, rt)
    params = model.init(jax.random.key(args.seed))
    eng = ServeEngine(model, params, n_slots=args.slots,
                      max_ctx=args.max_ctx, n_host_blocks=args.host_blocks)
    rng = np.random.default_rng(args.seed)
    rids = []
    for i in range(args.requests):
        plen = int(rng.integers(8, 48))
        toks = rng.integers(2, cfg.vocab_size, plen).tolist()
        kw = {}
        if cfg.prefix_len:
            kw["prefix_emb"] = 0.02 * jax.random.normal(
                jax.random.key(i), (min(cfg.prefix_len, 8), cfg.d_model))
        if cfg.n_enc_layers:
            kw["src_emb"] = 0.02 * jax.random.normal(
                jax.random.key(100 + i), (32, cfg.d_model))
        rids.append(eng.submit(toks, max_new=args.max_new, **kw))
    t0 = time.time()
    done = eng.run()
    wall = time.time() - t0
    stats = eng.kvm.hit_stats()
    out = {
        "completed": len(done),
        "generated_tokens": eng.metrics["generated"],
        "decode_steps": eng.metrics["decode_steps"],
        "preemptions": eng.metrics["preemptions"],
        "tok_per_s": round(eng.metrics["generated"] / max(wall, 1e-9), 1),
        "fmmu_map": stats.as_dict(),
        "pool_peak_blocks": eng.kvm.pool.stats.peak_used,
    }
    print(json.dumps(out, indent=2))
    for rid in rids[:3]:
        print(f"req {rid}: {done.get(rid, [])[:12]}")
    return done


if __name__ == "__main__":
    main()
