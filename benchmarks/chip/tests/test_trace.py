"""The trace reduction on a small hand-made trace (small_trace.pbtxt):
every number below is worked out by hand from that file."""
import os

import pytest

import xtrace as T

HERE = os.path.dirname(os.path.abspath(__file__))

PA = r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,1\], f32\[\d+,\d+,1\]\) custom-call$"


def load_small():
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "small_trace.pbtxt")) as f:
        text = f.read()
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return T.reduce(T.events_from_profile(pd))


@pytest.fixture(scope="module")
def reduced():
    return load_small()


def test_window_and_busy(reduced):
    # window: bench.window, 10 us; ops cover [1,4], [4.5,4.8], [6,8] us
    # (the op at 11 us lies outside the window)
    assert reduced.window_s == pytest.approx(10e-6)
    assert reduced.busy_s == pytest.approx(5.3e-6)
    assert reduced.idle_share == pytest.approx(0.47)
    assert reduced.n_devices == 1


def test_canonical_names():
    assert T.canonical(
        "%fusion.12 = bf16[8,4096]{1,0:T(8,128)(2,1)S(1)} fusion("
        "bf16[8,4096]{1,0} %a), kind=kOutput") == \
        "%fusion.12 = bf16[8,4096] fusion"
    assert T.canonical(
        "%c.1 = (bf16[8,32]{1,0:T(8,128)}, /*index=1*/f32[8,1]{1,0}) "
        "custom-call(s32[8]{0} %x)") == "%c.1 = (bf16[8,32], f32[8,1]) custom-call"
    assert T.canonical("jit_foo(12)") == "jit_foo(12)"


def test_leaves_only(reduced):
    # the loop's own event holds its body's ops: it is not a leaf
    assert not any(n.startswith("%while") for n in reduced.op_s)
    assert reduced.op_s["%fusion.12 = bf16[8,4096] fusion"] == \
        pytest.approx(1e-6)
    assert reduced.ops_matching(PA) == pytest.approx(1.5e-6)
    # the flash kernel: a single bf16 [B, H, S, D] result
    assert reduced.ops_matching(r"= bf16\[\d+,\d+,\d+,\d+\] custom-call$") \
        == pytest.approx(2e-6)


def test_modules(reduced):
    assert reduced.modules_matching(r"^jit__unknown\(") == \
        pytest.approx(3.3e-6)
    assert reduced.modules_matching(r"^jit__unknown\(", containing=PA) == \
        pytest.approx(3e-6)
    assert reduced.modules_matching(r"^jit__prefill_fn\(") == \
        pytest.approx(2e-6)


def test_idle_gaps_by_host_span(reduced):
    # gaps: [0,1] and [4,4.5] us in the first bench.step, [4.8,6] us with
    # its midpoint in bench.book, [8,10] us after the second step
    assert reduced.idle_by_span == pytest.approx(
        {"bench.step": 1.5e-6, "bench.book": 1.2e-6, "none": 2e-6})
    b = reduced.breakdown()
    assert b["device_ops"][0] == ["%closed_call.3 = bf16[1,32,512,128] "
                                  "custom-call", pytest.approx(2e-6)]
    assert [n for n, _ in b["idle_gaps"]] == ["none", "bench.step",
                                              "bench.book"]


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        T.reduce([T.Event("/host:CPU", "python3", "bench.window", 0, 10)])
