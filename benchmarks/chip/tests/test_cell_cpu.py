"""Whole runs of a cell on the CPU, at toy widths, skipping only the
harness's look for a chip.

The toy cells are added the way a later change adds one: new files
(tests/tiny/) and new entries in BENCHMARK.json, on a copy of the
benchmark, with no edit to any file the benchmark has. The runs check
that the harness finds them by name, that the served tokens pass the
comparison with the plain reference, that the fp8 control fails it, and
that a token altered where the engine produces it makes the run
incorrect. ``tiny_hybrid`` brings a model family of its own as a new
file (tests/tiny/families/tiny_hybrid.py): Mamba-2 and NoPE attention
layers, whose paged attention runs in half of the layers."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import flops
import harness
from dims import Dims

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
SECONDS = 3.0


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    shutil.copytree(os.path.join(HERE, "tiny"), bench, dirs_exist_ok=True)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for config in ("tiny", "tiny_hybrid"):
        b["configs"].append({"name": config, "source": "test",
                             "file": f"benchmarks/chip/configs/{config}.json",
                             "reduced": [], "why": "test"})
    cells = (("tiny.backlog", "tiny", "tiny_backlog", "decode_long"),
             ("tiny.chat", "tiny", "tiny_chat", "chat_open"),
             ("tiny_hybrid.backlog", "tiny_hybrid", "tiny_backlog",
              "decode_long"))
    for cell, config, mix, _ in cells:
        b["workloads"].append({"name": cell, "config": config,
                               "traffic": mix, "chips": 1, "why": "test"})
    for m in b["end_to_end"]:       # the tiny cells join their kind's
        if "workloads" in m:
            m["workloads"] += [tiny for tiny, _, _, kind in cells
                               if any(w.endswith(kind)
                                      for w in m["workloads"])]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(b, f)
    return root


def _run(root, name, seed, lines=None, control=False):
    cell = harness.load_cell(name, root=str(root),
                             bench_dir=str(root / "benchmarks" / "chip"))
    log = (lambda *a: lines.append(" ".join(map(str, a)))) \
        if lines is not None else (lambda *a: None)
    return harness.run_cell(cell, seed, SECONDS, trace=False,
                            t_start=time.perf_counter(), require_tpu=False,
                            control=control, log=log)


@pytest.fixture(scope="module")
def backlog(tiny_root):
    lines = []
    return _run(tiny_root, "tiny.backlog", 2**33 + 17, lines), lines


def test_backlog_cell_found_and_correct(backlog):
    out, lines = backlog
    assert out["correct"], lines
    assert set(out["metrics"]) == {"output_tok_s", "delivery_gap_p95_ms",
                                   "setup_s"}
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert any("compiles in window: 0" in ln for ln in lines)


def test_fp8_control_is_rejected(tiny_root):
    # the control's tokens go through the run's own comparison and limit
    lines = []
    out = _run(tiny_root, "tiny.backlog", 2**33 + 17, lines, control=True)
    assert not out["correct"], lines
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    # the served tokens of the same run pass it
    assert out["control"]["served_max_logit_gap"] <= gap["limit"]


def test_chat_cell_open_loop(tiny_root):
    lines = []
    out = _run(tiny_root, "tiny.chat", 5, lines)
    assert out["correct"], lines
    assert set(out["metrics"]) == {"delivery_gap_p95_ms", "setup_s"}
    # every request due in the window showed its first token
    assert out["failed"] == 0 and out["attempted"] > 5
    assert any(ln.startswith("ttft ms: p50") for ln in lines)


def test_altered_token_makes_run_incorrect(tiny_root, monkeypatch):
    from repro.serving.engine import ServeEngine
    book = ServeEngine._macro_book_simple
    calls = [0]

    def altered(self, residents, toks, pend, K, done):
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks = toks.copy()
            toks[K // 2] = (toks[K // 2] + 1) % self.cfg.vocab_size
        return book(self, residents, toks, pend, K, done)

    monkeypatch.setattr(ServeEngine, "_macro_book_simple", altered)
    out = _run(tiny_root, "tiny.backlog", 7)
    assert not out["correct"]
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "mistral-7b.decode_long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout


@pytest.fixture(scope="module")
def hybrid(tiny_root):
    """One run of the hybrid cell, with the run the harness observed and
    the (first keys, tokens) of every paged-attention count it booked."""
    bench = tiny_root / "benchmarks" / "chip"
    cell = harness.load_cell("tiny_hybrid.backlog", root=str(tiny_root),
                             bench_dir=str(bench))
    booked, runs, lines = [], [], []
    count, compare = cell.family.paged_attn_run, harness._compare

    def counted(d, first, n):
        booked.append((first, n))
        return count(d, first, n)

    def kept(run, *a):
        runs.append(run)
        return compare(run, *a)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cell.family, "paged_attn_run", counted)
        mp.setattr(harness, "_compare", kept)
        out = harness.run_cell(
            cell, 2**33 + 29, SECONDS, trace=False,
            t_start=time.perf_counter(), require_tpu=False,
            log=lambda *a: lines.append(" ".join(map(str, a))))
    return cell, out, runs[0], booked, lines


def test_hybrid_cell_found_and_correct(hybrid):
    cell, out, _, _, lines = hybrid
    assert cell.family.__name__ == "family_tiny_hybrid"
    assert cell.family.layer_kinds(cell.dims) == ["mamba", "attn"] * 2
    assert out["correct"], lines
    assert out["failed"] == 0 and out["attempted"] > 0
    assert any("compiles in window: 0" in ln for ln in lines)


def test_hybrid_paged_attention_counts_attention_layers(hybrid):
    cell, _, run, booked, _ = hybrid
    d = cell.dims
    assert booked and run.pa_bytes > 0

    def one_layer(first, n):     # live K and V of each key, q and out
        return sum(2 * (first + i) * d.n_kv_heads * d.head_dim * 2
                   + 2 * d.n_heads * d.head_dim * 2 for i in range(n))

    assert run.pa_bytes == 2 * sum(one_layer(f, n) for f, n in booked)
    # the dense count takes every one of the four layers
    dense = Dims(n_layers=d.n_layers, d_model=d.d_model, n_heads=d.n_heads,
                 n_kv_heads=d.n_kv_heads, head_dim=d.head_dim, d_ff=d.d_ff,
                 vocab=d.vocab, qkv_bias=False, rope_theta=1e4,
                 norm_eps=d.norm_eps, max_ctx=d.max_ctx)
    assert sum(flops.paged_attn_run(dense, f, n)[1] for f, n in booked) \
        == 2 * run.pa_bytes


def test_hybrid_fp8_control_is_rejected(tiny_root):
    lines = []
    out = _run(tiny_root, "tiny_hybrid.backlog", 2**33 + 29, lines,
               control=True)
    assert not out["correct"], lines
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert out["control"]["served_max_logit_gap"] <= gap["limit"]
