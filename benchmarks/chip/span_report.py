#!/usr/bin/env python3
"""One traced run of a cell, reported by program span and device scope.

    python3 benchmarks/chip/span_report.py --workload <name> --seed <n> \\
        --seconds <s> [--out <file.json>]

Runs the cell exactly as ``run.py --trace 1`` does (``harness.run_cell``)
and reduces the same trace a second time with xspans.py before the
harness deletes it. Prints the result line, then one JSON object:

- ``spans_s`` and ``idle_by_span``: seconds of each ``bench.*`` and
  ``serve.*`` span in the window, and the device's idle seconds charged
  to the innermost span around each gap; ``longest_gaps``: the longest
  gaps, each with its span and its start in the window;
- ``kernels``: device seconds of each Pallas kernel found by its scope
  (``name=`` on its ``pallas_call``) beside those found by its result
  type, as the accepted readers find them, and the ops in the scope
  that are not the kernel;
- ``pool_ops``: the pool-shaped device ops (a result that ends in the
  pool's ``[blocks, page, kv_heads * head_dim]``) by scope, so what the
  ``kv_pool`` scope catches and what it misses are both in view;
- ``derived``: the per-layer numbers these spans and scopes give:
  ``kv_pool_share`` (% of busy time in ops under the ``kv_pool``
  scope), ``boundary_host_ms`` ((``serve.step`` - ``serve.sync``) per
  decode dispatch) and ``admit_ms`` (``serve.admit`` per prefill).

It needs the chip, as run.py does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH_DIR)),
                                "src"))

import xspans  # noqa: E402
import xtrace  # noqa: E402

# result types, as decode_step_ms.*, paged_attention_roofline.decode and
# fmmu_translate_share.decode find their kernels
BY_TYPE = {
    "_pa_kernel": r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,1\], "
                  r"f32\[\d+,\d+,1\]\) custom-call$",
    "_ft_kernel": r"= \((s32\[\d+,1\], ){4}s32\[\d+,\d+\]\) custom-call$",
    "_fa_kernel": r"= bf16\[\d+,\d+,\d+,\d+\] custom-call$",
}


def derived(sp: xspans.Spans) -> dict:
    """The per-layer numbers of the spans and scopes, per macro (or
    single) step and per prefill counted by their spans in the window
    (None where the window has none)."""
    s, n = sp.spans_s, sp.spans_n
    steps, prefills = n.get("serve.dispatch", 0), n.get("serve.prefill", 0)
    return {
        "kv_pool_share": (sp.scopes_matching(r"/kv_pool/") / sp.busy_s
                          * 100 if sp.busy_s > 0 else None),
        "boundary_host_ms": ((s.get("serve.step", 0.0)
                              - s.get("serve.sync", 0.0)) / steps * 1e3
                             if steps else None),
        "admit_ms": (s.get("serve.admit", 0.0) / prefills * 1e3
                     if prefills else None),
    }


def kernel(sp: xspans.Spans, red: xtrace.Reduced, name: str,
           by_type: str) -> dict:
    """Device seconds of one Pallas kernel found by its scope (every
    op, and its custom calls alone) and by its result type, with the
    ops the scope holds that the result type does not match."""
    ops = sp.ops_in_scope(f"/{name}/")
    rx = re.compile(by_type)
    return {"by_scope_s": sum(ops.values()),
            "by_scope_custom_call_s": sum(
                s for n, s in ops.items() if n.endswith(" custom-call")),
            "by_result_type_s": red.ops_matching(by_type),
            "scope_only": {n: s for n, s in ops.items() if not rx.search(n)}}


def report(sp: xspans.Spans, red: xtrace.Reduced, pool_tail: str) -> dict:
    """What one trace shows by span and scope; ``pool_tail`` is the
    result type's tail that marks a pool-shaped op (``"8193,16,1024]"``)."""
    in_step = sum(v for k, v in sp.idle_by_span.items()
                  if k == "bench.step" or k.startswith("serve."))
    pool = re.compile(r"^\S+ = bf16\[[\d,]*" + re.escape(pool_tail))
    pool_ops: dict = {}
    for (name, scope), sec in sorted(sp.leaf_s.items(),
                                     key=lambda kv: -kv[1]):
        if pool.search(name):
            pool_ops.setdefault(scope or "(none)", []).append([name, sec])
    return {
        "window_s": sp.window_s, "busy_s": sp.busy_s,
        "spans_s": sp.spans_s, "idle_by_span": sp.idle_by_span,
        "longest_gaps": sp.gaps,
        "idle_in_bench_step_s": in_step,
        "idle_in_bench_step_to_serve": (
            sum(v for k, v in sp.idle_by_span.items()
                if k.startswith("serve.")) / in_step if in_step else None),
        "kernels": {k: kernel(sp, red, k, rx) for k, rx in BY_TYPE.items()},
        "pool_ops": pool_ops,
        "stat_names": sp.stat_names,
        "derived": derived(sp),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import harness
    import run as run_mod
    cell = harness.load_cell(args.workload)
    run_mod.log(f"span_report: {args.workload} seed {args.seed}; compile "
                f"cache {run_mod.enable_compile_cache()}")
    # the harness reduces its trace and deletes it, keeping no handle on
    # the directory: read it here first, for both reductions
    seen = []

    def load_dir(trace_dir):
        events, table = xspans.load_dir(trace_dir)
        seen.append((xspans.reduce(events, table), xtrace.reduce(events)))
        return events

    xtrace.load_dir = load_dir
    out = harness.run_cell(cell, args.seed, args.seconds, trace=True,
                           t_start=T_START, log=run_mod.log)
    print(json.dumps(out), flush=True)
    d, page = cell.dims, int(cell.serving["page_size"])
    blocks = int(cell.serving["n_slots"]) * (d.max_ctx // page) + 1
    rep = report(*seen[0], f"{blocks},{page},{d.n_kv_heads * d.head_dim}]")
    rep["workload"], rep["seed"] = args.workload, args.seed
    text = json.dumps(rep)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
