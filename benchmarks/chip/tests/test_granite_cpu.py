"""Whole runs of a granitemoehybrid cell on the CPU at toy widths, the
family found by name (families/granitemoehybrid.py, the one that serves
granite-4.0-h-small): Mamba-2 and NoPE attention layers, a router over
8 experts of which this chip holds 2, a shared expert and Granite's
multipliers. As in test_cell_cpu.py the toy cell is added as new files
(tests/tiny/configs/tiny_granite.json, tests/tiny/cells/) and a new
BENCHMARK.json entry on a copy of the benchmark.

The toy limit, 0.0005 (tests/tiny/cells/tiny_granite.backlog.json): the
logits' spread is ~0.0013 (the tied head's embedding std, divided by
logits_scaling 16; families/granitemoehybrid.make_outer), so gaps are
small; 8 sound runs on 4 seeds read at most 0.000246 and 4 fp8 controls
at least 0.00075 (CPU, bf16 serving at these widths)."""
import json
import os
import shutil
import time

import pytest

import harness

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
SECONDS = 3.0
CELL = "tiny_granite.backlog"


@pytest.fixture(scope="module")
def granite_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "tiny"), bench, dirs_exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny_granite", "source": "test",
                         "file": "benchmarks/chip/configs/tiny_granite.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": CELL, "config": "tiny_granite",
                           "traffic": "tiny_backlog", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "granite-4.0-h-small.decode_long" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return root


def _run(root, seed, lines, control=False):
    cell = harness.load_cell(CELL, root=str(root),
                             bench_dir=str(root / "benchmarks" / "chip"))
    out = harness.run_cell(cell, seed, SECONDS, trace=False,
                           t_start=time.perf_counter(), require_tpu=False,
                           control=control,
                           log=lambda *a: lines.append(" ".join(map(str, a))))
    return cell, out


def test_granite_cell_found_and_correct(granite_root):
    lines = []
    cell, out = _run(granite_root, 2**33 + 41, lines)
    assert cell.family.__name__ == "family_granitemoehybrid"
    d = cell.dims
    assert (d.n_experts, d.n_held, d.shared_ff) == (8, 2, 64)
    assert cell.family.layer_kinds(d) == ["mamba", "attn"] * 2
    assert out["correct"], lines
    assert set(out["metrics"]) == {"output_tok_s", "delivery_gap_p95_ms",
                                   "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles in window: 0" in ln for ln in lines)


def test_granite_fp8_control_is_rejected(granite_root):
    lines = []
    _, out = _run(granite_root, 2**33 + 41, lines, control=True)
    assert not out["correct"], lines
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["control"]["served_max_logit_gap"] <= gap["limit"]


def _granite_run(leaves, modules):
    import types
    import peaks
    import xtrace
    with open(os.path.join(BENCH, "configs", "granite-4.0-h-small.json")) as f:
        config = json.load(f)
    fam = harness.load_family("granitemoehybrid")
    trace = xtrace.Reduced(window_s=1.0, busy_s=1.0, n_devices=1, op_s={},
                           module_s={}, idle_by_span={}, spans_s={},
                           modules=modules, leaves=leaves)
    return types.SimpleNamespace(
        trace=trace, peaks=peaks.PEAKS["TPU v5 lite"],
        cell=types.SimpleNamespace(family=fam, dims=fam.dims(config)))


def test_granite_kernel_readers():
    """ssm_step_roofline.decode and moe_gmm_roofline.decode on a hand-made
    trace at granite-4.0-h-small's widths: the calls inside the decode
    scans count, at the least time of their work over their time; those
    in other executables (prefill) do not."""
    pa = "%c.1 = (bf16[64,32,1024], f32[64,32,1], f32[64,32,1]) custom-call"
    ssm = "%c.2 = (f32[9,64,128,64,128], f32[64,128,64]) custom-call"
    up, down = ("%c.3 = bf16[640,768] custom-call",
                "%c.4 = bf16[640,4096] custom-call")
    us = 1e3                                    # ns
    leaves = [(0, us, pa), (us, 3 * us, ssm), (3 * us, 4 * us, up),
              (4 * us, 6 * us, down),
              (20 * us, 25 * us, ssm), (25 * us, 26 * us, up)]  # prefill
    modules = [("jit__unknown(abc)", 0, 10 * us),
               ("jit__prefill_fn(abc)", 20 * us, 30 * us)]
    run = _granite_run(leaves, modules)
    d, p = run.cell.dims, run.peaks
    fam = run.cell.family
    f, b = fam.ssm_step_call(d, 64)
    # 64 lanes x 4 MiB of f32 state, read and written: 512 MiB and more
    assert b > 2 * 64 * 128 * 64 * 128 * 4
    want = max(b / p["hbm_bytes_s"], f / p["bf16_flops_s"]) / 2e-6 * 100
    got = harness.load_reader("ssm_step_roofline.decode")(run)
    assert got == pytest.approx(want)
    least = 0.0
    for rows, n in ((640, 768), (640, 4096)):
        f, b = fam.moe_gmm_call(d, rows, n)
        least += max(b / p["hbm_bytes_s"], f / p["bf16_flops_s"])
    # the held experts' weights dominate: 18 x 4096 x 768 bf16 a call
    assert fam.moe_gmm_call(d, 640, 768)[1] > 18 * 4096 * 768 * 2
    got = harness.load_reader("moe_gmm_roofline.decode")(run)
    assert got == pytest.approx(least / 3e-6 * 100)
    # a trace without the kernels (or without a scan) reads nothing
    bare = _granite_run([(0, us, pa)], modules)
    assert harness.load_reader("ssm_step_roofline.decode")(bare) is None
    assert harness.load_reader("moe_gmm_roofline.decode")(bare) is None
