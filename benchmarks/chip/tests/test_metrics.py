"""Each metric file's reader against numbers worked out by hand, on the
small trace (small_trace.pbtxt) and a hand-filled run record."""
import types

import pytest

import harness
import peaks
from test_trace import load_small

V5E = peaks.PEAKS["TPU v5 lite"]


@pytest.fixture(scope="module")
def run():
    return types.SimpleNamespace(
        trace=load_small(), peaks=V5E, window_s=10e-6, setup_s=42.5,
        tokens_out=20, gaps_s=[0.1] * 19 + [1.1], ttft_s=[0.2, 0.4],
        queue_wait_s=[0.0, 0.1], prompt_tokens=512,
        model_flops=197e12 * 1e-6,              # 1 us of peak
        pa_bytes=819e9 * 1e-6, pa_flops=1.0,    # 1 us of HBM bandwidth
        engine_delta={"engine.decode_steps": 8, "engine.generated": 40,
                      "engine.prefills": 8},
        cell=types.SimpleNamespace(serving={"n_slots": 4}))


def read(name, run):
    return harness.load_reader(name)(run)


@pytest.mark.parametrize("name,want", [
    ("output_tok_s", 20 / 10e-6),
    # p95 of 19 gaps of 0.1 s and one of 1.1 s: rank 18.05 of 0..19
    ("delivery_gap_p95_ms", 150.0),
    ("setup_s", 42.5),
    ("device_idle_share.decode", 47.0),
    ("device_idle_share.chat", 47.0),
    # 3 us of decode-scan executables (the one running the paged kernel)
    # over 8 token steps
    ("decode_step_ms.decode", 3e-6 / 8 * 1e3),
    ("decode_step_ms.chat", 3e-6 / 8 * 1e3),
    ("mfu.decode", 10.0),
    # 1 us of bytes over 1.5 us of kernel
    ("paged_attention_roofline.decode", 100 / 1.5),
    # translate kernels 0.5 + 0.3 us over 5.3 us busy
    ("fmmu_translate_share.decode", 0.8 / 5.3 * 100),
    # (40 generated - 8 first tokens) / (8 steps x 4 slots)
    ("lane_occupancy.decode", 100.0),
    # 2 us of prefill executables over 512 prompt tokens, per 1000
    ("prefill_ms_per_ktok.chat", 2e-6 / 512 * 1e6),
])
def test_reader(run, name, want):
    assert read(name, run) == pytest.approx(want)


def test_readers_without_trace_return_nothing(run):
    bare = types.SimpleNamespace(**{**vars(run), "trace": None})
    for name in ("device_idle_share.decode", "decode_step_ms.decode",
                 "mfu.decode", "paged_attention_roofline.decode",
                 "fmmu_translate_share.decode", "prefill_ms_per_ktok.chat"):
        assert read(name, bare) is None
