#!/usr/bin/env python3
"""Benchmark entry: one run of one cell of BENCHMARK.json on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
the same window. The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number
compared with its limit); the last lines of standard error repeat the
checks. A run that finds no TPU, or fewer chips than the cell asks for,
or compiles inside the measured window, exits non-zero and prints no
result. ``--control 1`` judges, in place of the served tokens, those
the fp8 reference puts first at the same positions (the control, which
must come out not correct; for calibration only).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout (or
    where JAX_COMPILATION_CACHE_DIR says), every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    try:
        cell = harness.load_cell(args.workload)
    except (OSError, KeyError, IndexError, harness.BenchError) as e:
        log(f"bench: cannot load cell {args.workload!r}: {e!r}")
        return 2
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"bench: needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s)")
        return 3
    log(f"bench: {args.workload} seed {args.seed} on {devs[0].device_kind} "
        f"x {len(devs)}; compile cache {enable_compile_cache()}")
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               trace=bool(args.trace), t_start=T_START,
                               control=bool(args.control), log=log)
    except harness.BenchError as e:
        log(f"bench: {e}")
        return 4
    log(f"correct: {out['correct']}")
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
