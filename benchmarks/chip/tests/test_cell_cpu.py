"""Whole runs of a cell on the CPU, at toy widths, skipping only the
harness's look for a chip.

The toy cells are added the way a later change adds one: new files
(tests/tiny/) and new entries in BENCHMARK.json, on a copy of the
benchmark, with no edit to any file the benchmark has. The runs check
that the harness finds them by name, that the served tokens pass the
comparison with the plain reference, that the fp8 control fails it, and
that a token altered where the engine produces it makes the run
incorrect."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

import harness

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
    b["configs"].append({"name": "tiny", "source": "test",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "test"})
    for cell, mix in (("tiny.backlog", "tiny_backlog"),
                      ("tiny.chat", "tiny_chat")):
        b["workloads"].append({"name": cell, "config": "tiny",
                               "traffic": mix, "chips": 1, "why": "test"})
    for m in b["end_to_end"]:       # the tiny cells join their kind's
        if "workloads" in m:
            m["workloads"] += [tiny for tiny, kind in (
                ("tiny.backlog", "decode_long"), ("tiny.chat", "chat_open"))
                if any(w.endswith(kind) for w in m["workloads"])]
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
