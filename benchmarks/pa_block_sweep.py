#!/usr/bin/env python3
"""Paged-attention kernel alone on the TPU: device time and roofline
share against the bytes per block (so pages per block), at the decode
widths of the chip benchmark's dense configurations.

    python3 benchmarks/pa_block_sweep.py --out pa_sweep.json \\
        [--block-kib 128 256 512 1024] [--baseline path/to/kernel.py ...]

Each width gets its bf16 stacked pool [L, NB, 16, KV*D], a 256-page
bucket and contexts drawn uniformly between 1024 and 4096 keys;
one jitted call runs the kernel over all L layers (a ``fori_loop``, as
the decode scan does), so the time per call is the device's. The block
size is set by ``BLOCK_BYTES``, the module constant that
``pages_per_block`` reads. ``--baseline`` times other kernel modules
with the same ``paged_attention`` signature (an older version, say) on
the same inputs, each as it stands. The roofline share is the larger
of the live K/V bytes over HBM bandwidth and the useful FLOPs over the
bf16 peak, divided by the time, with the v5e's published peaks.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

HBM_BYTES_S, BF16_FLOPS_S = 819e9, 197e12      # TPU v5e, published
PAGE, BUCKET = 16, 256
MIN_CTX, SEED, REPS = 1024, 1, 20
WIDTHS = {  # name: (B, H, KV, D, L), the decode_long cells' widths
    "mistral-7b": (32, 32, 8, 128, 8),
    "glm4-9b": (16, 32, 2, 128, 20),
}


def _load(path):
    spec = importlib.util.spec_from_file_location("pa_baseline", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _inputs(name):
    import jax
    import jax.numpy as jnp
    import numpy as np
    b, h, kv, d, n_layers = WIDTHS[name]
    rng = np.random.default_rng(SEED)
    nb = b * BUCKET + 1
    table = rng.permutation(nb - 1)[:b * BUCKET].reshape(b, BUCKET) + 1
    ctx = rng.integers(MIN_CTX, BUCKET * PAGE + 1, size=b)
    key = jax.random.key(SEED)
    pool = (n_layers, nb, PAGE, kv * d)
    q = jax.random.normal(key, (b, h, d), jnp.bfloat16)
    kp = jax.random.normal(jax.random.fold_in(key, 1), pool, jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 2), pool, jnp.bfloat16)
    return (q, kp, vp, jnp.asarray(table, jnp.int32),
            jnp.asarray(ctx, jnp.int32)), ctx


def _time_call(mod, args, n_layers):
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.jit
    def run(q, kp, vp, table, ctx):
        def one(li, acc):
            out = mod.paged_attention(q, kp, vp, table, ctx, layer=li)
            return acc + out.astype(jnp.float32)
        return lax.fori_loop(0, n_layers, one,
                             jnp.zeros(q.shape, jnp.float32))

    jax.block_until_ready(run(*args))
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = run(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS / n_layers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--block-kib", type=int, nargs="+",
                    default=[64, 128, 256, 512, 1024, 2048])
    ap.add_argument("--baseline", nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax
    from repro.kernels import paged_attention as pa

    rows = []
    for name, (b, h, kv, d, n_layers) in WIDTHS.items():
        inputs, ctx = _inputs(name)
        keys = int(ctx.sum())
        nbytes = 2 * keys * kv * d * 2 + 2 * b * h * d * 2
        least = max(nbytes / HBM_BYTES_S, 4 * h * d * keys / BF16_FLOPS_S)
        cases = [(f"block_{kib}KiB", pa, kib) for kib in args.block_kib]
        cases += [(os.path.basename(path), _load(path), None)
                  for path in args.baseline]
        for label, mod, kib in cases:
            row = {"width": name, "case": label, "keys": keys,
                   "floor_us": least * 1e6}
            if kib is not None:
                pa.BLOCK_BYTES = kib << 10
                row["ppb"] = pa.pages_per_block(PAGE, kv * d, 2, BUCKET)
            try:
                t = _time_call(mod, inputs, n_layers)
                row.update(us_per_call=t * 1e6,
                           roofline_pct=least / t * 100)
            except Exception as e:          # a block VMEM cannot hold
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(row), flush=True)
            rows.append(row)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"device": str(jax.devices()[0].device_kind),
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
