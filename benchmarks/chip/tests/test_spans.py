"""The span and scope reduction (xspans.py) on a hand-made trace
(small_trace_spans.pbtxt): every number below is worked out by hand from
that file. On small_trace.pbtxt it must agree with xtrace.py."""
import os

import pytest

import span_report
import xspans
import xtrace
from test_trace import PA

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


def serialized(name):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, name)) as f:
        return ProfileData.text_proto_to_serialized_xspace(f.read())


def load(name):
    from jax.profiler import ProfileData
    raw = serialized(name)
    events = xtrace.events_from_profile(
        ProfileData.from_serialized_xspace(raw))
    return events, xspans.scope_table(raw)


@pytest.fixture(scope="module")
def spans():
    return xspans.reduce(*load("small_trace_spans.pbtxt"))


def test_scope_table_reads_metadata_stats():
    (table,) = xspans.scope_table(serialized("small_trace_spans.pbtxt")) \
        .values()
    by_head = {n.split(" = ")[0]: st for n, st in table.items()}
    assert by_head["%dynamic-slice_bitcast_fusion.4"] == {
        "tf_op": "jit(_macro_fn)/while/body/closed_call/while/body/"
                 "closed_call/kv_pool/squeeze:"}
    assert by_head["%copy.192"] == {"tf_op": "jit(_macro_fn)/while:"}
    assert by_head["%fusion.22"] == {}
    # host planes are not read
    assert "bench.step" not in table


def test_window_busy_and_spans(spans):
    # ops cover [2.6,5.0] [5.4,5.6] [7.6,11.6] [12.7,13.0] [13.4,14.1]
    # [14.3,15.2] [15.8,16.0] us of a 20 us window; one op at 21 us is out
    assert spans.window_s == pytest.approx(20 * US)
    assert spans.busy_s == pytest.approx(8.7 * US)
    assert spans.spans_s == pytest.approx({
        "bench.step": 14 * US, "bench.book": 1 * US,
        "serve.step": 13 * US, "serve.admit": 4 * US,
        "serve.map": (0.5 + 0.5 + 0.4) * US, "serve.prefill": 3.5 * US,
        "serve.sync": (1 + 4.5) * US, "serve.plan": (0.5 + 0.2) * US,
        "serve.dispatch": 0.5 * US, "serve.book": 1.4 * US})
    assert spans.spans_n["serve.sync"] == 2
    assert spans.spans_n["serve.dispatch"] == 1
    assert spans.stat_names == ["tf_op"]


def test_idle_charged_to_innermost_span(spans):
    # gap [0,2.6] mid 1.3: bench.step alone (serve.step opens at 1.5);
    # [5.0,5.4]: serve.sync, four deep inside the prefill;
    # [5.6,7.6] mid 6.6 and [11.6,12.7] mid 12.15: serve.plan;
    # [13.0,13.4]: serve.book; [14.1,14.3] mid 14.2: serve.step itself,
    # after seven children that closed before it; [15.2,15.8]:
    # bench.book; [16,20]: no span
    assert spans.idle_by_span == pytest.approx({
        "bench.step": 2.6 * US, "serve.sync": 0.4 * US,
        "serve.plan": (2.0 + 1.1) * US, "serve.book": 0.4 * US,
        "serve.step": 0.2 * US, "bench.book": 0.6 * US, "none": 4.0 * US})
    assert sum(spans.idle_by_span.values()) == \
        pytest.approx(spans.window_s - spans.busy_s)
    assert spans.gaps[:3] == pytest.approx([
        (4.0 * US, "none", 16 * US), (2.6 * US, "bench.step", 0.0),
        (2.0 * US, "serve.plan", 5.6 * US)])


def test_scopes(spans):
    assert spans.scopes_matching(r"/kv_pool/") == pytest.approx(1.0 * US)
    assert spans.ops_in_scope(r"/kv_pool/") == pytest.approx(
        {"%dynamic-slice_bitcast_fusion.4 = bf16[9,16,256] fusion": 1.0 * US})
    assert spans.scopes_matching(r"/_pa_kernel/") == pytest.approx(1.0 * US)
    assert spans.scopes_matching(r"/_ft_kernel/") == \
        pytest.approx((0.5 + 0.3) * US)
    assert spans.scopes_matching(r"/_fa_kernel/") == pytest.approx(2.0 * US)
    # the while loop is not a leaf; one fusion carries no metadata
    assert not any(n.startswith("%while") for n in spans.ops_in_scope(""))
    assert spans.ops_in_scope(r"^jit\(_macro_fn\)/while:$") == \
        pytest.approx({"%copy.192 = bf16[2,1,9,16,256] copy": 0.5 * US})
    assert spans.unscoped() == pytest.approx(
        {"%fusion.22 = s32[8] fusion": 0.2 * US})


def test_report_scope_beside_result_type(spans):
    events, _ = load("small_trace_spans.pbtxt")
    rep = span_report.report(spans, xtrace.reduce(events), "9,16,256]")
    for k in ("_pa_kernel", "_ft_kernel", "_fa_kernel"):
        got = rep["kernels"][k]
        assert got["by_scope_s"] == got["by_scope_custom_call_s"] == \
            pytest.approx(got["by_result_type_s"])
        assert got["scope_only"] == {}
    assert rep["kernels"]["_pa_kernel"]["by_result_type_s"] == \
        pytest.approx(1.0 * US)
    assert {k: [n for n, _ in v] for k, v in rep["pool_ops"].items()} == {
        "jit(_macro_fn)/while/body/closed_call/while/body/closed_call/"
        "kv_pool/squeeze:": ["%dynamic-slice_bitcast_fusion.4 = "
                             "bf16[9,16,256] fusion"],
        "jit(_macro_fn)/while/body/closed_call/while/body/"
        "dynamic_update_slice:": ["%bitcast_dynamic-update-slice_fusion.4 "
                                  "= bf16[2,1,9,16,256] fusion"],
        "jit(_macro_fn)/while:": ["%copy.192 = bf16[2,1,9,16,256] copy"]}
    # idle inside bench.step: 2.6 + 0.4 + 3.1 + 0.4 + 0.2 us, of which
    # all but the first 2.6 us falls to a serve.* span
    assert rep["idle_in_bench_step_s"] == pytest.approx(6.7 * US)
    assert rep["idle_in_bench_step_to_serve"] == pytest.approx(4.1 / 6.7)
    assert rep["derived"] == pytest.approx({
        # 1 us under kv_pool over 8.7 us busy
        "kv_pool_share": 100 / 8.7,
        # (13 us of serve.step - 5.5 us of serve.sync) over one dispatch
        "boundary_host_ms": 7.5e-3,
        # 4 us of serve.admit over one prefill
        "admit_ms": 4e-3})


def test_agrees_with_xtrace_on_small_trace():
    events, table = load("small_trace.pbtxt")
    sp, red = xspans.reduce(events, table), xtrace.reduce(events)
    assert sp.window_s == pytest.approx(red.window_s)
    assert sp.busy_s == pytest.approx(red.busy_s)
    assert sp.idle_by_span == pytest.approx(red.idle_by_span)
    assert sp.spans_s == pytest.approx(red.spans_s)
    # no op of that trace carries metadata
    assert sp.unscoped() == pytest.approx(red.op_s)
    assert red.ops_matching(PA) == pytest.approx(1.5 * US)
