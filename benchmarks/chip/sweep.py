#!/usr/bin/env python3
"""Rate sweep of an open-loop cell, to find the highest arrival rate the
system sustains without a growing queue (run once, on the chip; the
cell then fixes its rate as a number in cells/<name>.json).

    python3 benchmarks/chip/sweep.py --workload W --rates 2 4 6 \\
        --seconds 30 --seed 1

One process: the engine is built and warmed once, then each rate gets
its own window on the same engine, and the engine is stepped empty
between rates. Per rate it prints the output rate, the TTFT and queue
wait tails, the mean number of requests waiting for a slot in each
half of the window, and how many still waited when it closed: a queue
that is longer in the second half than in the first is growing, and the
rate is over capacity.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

import harness  # noqa: E402


class QueueDriver(harness.Driver):
    """Books, after each step, how many requests still wait for a slot."""

    def __init__(self, eng, run):
        super().__init__(eng, run)
        self.queued = []

    def step(self, in_window: bool):
        t0, t1 = super().step(in_window)
        self.queued.append((t1, len(self.eng.queue)))
        return t0, t1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    import peaks
    import traffic
    from run import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 3
    enable_compile_cache()
    cell = harness.load_cell(args.workload)
    plans = {r: traffic.make_plan(
        cell.mix, n_slots=int(cell.serving["n_slots"]),
        max_ctx=cell.dims.max_ctx, vocab=cell.dims.vocab, seed=args.seed,
        seconds=args.seconds, rate_per_s=r) for r in args.rates}
    watch = harness.CompileWatch()
    eng = harness.build_engine(cell, args.seed)
    harness.warm_up(eng, list(plans.values()), cell.dims.vocab,
                    np.random.default_rng(0))
    print(f"set-up {time.perf_counter() - T_START:.1f} s", flush=True)
    for rate in args.rates:
        run = harness.Run(cell=cell, seed=args.seed, seconds=args.seconds,
                          peaks=peaks.peaks_for(jax.devices()[0].device_kind))
        drv = QueueDriver(eng, run)
        harness.measure(eng, drv, run, plans[rate], args.seconds,
                        time.perf_counter(), watch)
        waiting = sum(1 for r in run.requests.values()
                      if r.admitted is None or r.admitted > run.t_close)
        half = run.t_open + run.window_s / 2
        q1 = [n for t, n in drv.queued if run.t_open <= t < half]
        q2 = [n for t, n in drv.queued if half <= t <= run.t_close]
        print(f"rate {rate}: {run.tokens_out / run.window_s:.1f} tokens/s, "
              f"ttft p50 {harness.pct(run.ttft_s, 50) * 1e3:.1f} ms p95 "
              f"{harness.pct(run.ttft_s, 95) * 1e3:.1f} ms, queue wait p95 "
              f"{harness.pct(run.queue_wait_s, 95) * 1e3:.1f} ms, gap p95 "
              f"{harness.pct(run.gaps_s, 95) * 1e3:.1f} ms, "
              f"{len(run.ttft_s)} requests, queue mean {np.mean(q1):.2f} "
              f"then {np.mean(q2):.2f} (halves), {waiting} waiting at close, "
              f"compiles in window {len(run.compiles_in_window)}",
              flush=True)
        while eng.step(drv.done):
            pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
