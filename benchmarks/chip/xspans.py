"""What a profiler trace holds beyond xtrace.py's reduction: the
program's own host spans, the innermost span around each idle gap, and
the scope path of each device op.

- Host spans of both families are kept: the harness's ``bench.*`` and
  the program's ``serve.*`` (``ServeEngine.step``, through
  ``repro.core.counters.span``), as seconds inside the window by name.
- Each idle gap of the first device is charged to the innermost span,
  of either family, that encloses the gap's midpoint, however many
  siblings started before it (xtrace.py looks only at the four spans
  that started last, which suits the harness's shallow nesting alone).
- Each device leaf op carries its scope path: the ``op_name`` metadata
  JAX gives every op (``jax.named_scope`` and a ``pallas_call``'s
  ``name=`` add a component). A v5e trace keeps it as the ``tf_op``
  stat of the op's event metadata, with a ":" appended
  (``jit(_macro_fn)/while/body/.../kv_pool/squeeze:``); the copies XLA
  inserts around a loop carry the loop's own path (``jit(_macro_fn)/
  while:``). ``ProfileData`` does not read metadata stats, so
  ``scope_table`` reads them from the serialized ``XSpace``. An op with
  no such stat (a ``copy-start``, for one) has the scope ``""``.

Window, device planes, leaves and busy time follow xtrace.py exactly,
so the two reductions agree on every number they share.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

import xtrace

SPAN_PREFIXES = ("bench.", "serve.")
SCOPE_STATS = ("tf_op",)


# ------------------------------------------------- serialized XSpace
def _varint(b, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message; a length-delimited
    value is a memoryview of its bytes, a fixed one its raw bytes."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wire in (1, 5):
            ln = 8 if wire == 1 else 4
            v, i = b[i:i + ln], i + ln
        else:
            raise ValueError(f"protobuf wire type {wire} not supported")
        yield key >> 3, v


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def scope_table(xspace: bytes) -> Dict[str, Dict[str, Dict[str, str]]]:
    """plane name -> event name -> {stat name: value} of the string
    stats on each event metadata of the device planes (XSpace field 1:
    planes; XPlane 2: name, 4: event_metadata, 5: stat_metadata;
    XEventMetadata 2: name, 5: stats; XStat 1: metadata_id, 5:
    str_value, 7: ref_value, a stat metadata's name)."""
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        name, evmeta, statname = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g in (4, 5):
                entry = dict(_fields(v))
                if 2 not in entry:
                    continue
                if g == 4:
                    evmeta.append(entry[2])
                else:
                    sm = dict(_fields(entry[2]))
                    statname[sm.get(1, entry.get(1, 0))] = _text(
                        sm.get(2, b""))
        if not name.startswith("/device:"):
            continue
        table = out.setdefault(name, {})
        for em in evmeta:
            ev_name, stats = "", {}
            for g, v in _fields(em):
                if g == 2:
                    ev_name = _text(v)
                elif g == 5:
                    st = dict(_fields(v))
                    key = statname.get(st.get(1, 0), "")
                    if 5 in st:
                        stats[key] = _text(st[5])
                    elif 7 in st:
                        stats[key] = statname.get(st[7], "")
            table[ev_name] = stats
    return out


def _scope(stats: Dict[str, str]) -> str:
    for k in SCOPE_STATS:
        if stats.get(k):
            return stats[k]
    return ""


# --------------------------------------------------------- loading
def load_dir(trace_dir: str):
    """(events, scope table) of every ``.xplane.pb`` under the dir."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[xtrace.Event] = []
    table: Dict[str, Dict[str, Dict[str, str]]] = {}
    for p in paths:
        with open(p, "rb") as f:
            raw = f.read()
        events += xtrace.events_from_profile(
            ProfileData.from_serialized_xspace(raw))
        for plane, t in scope_table(raw).items():
            table.setdefault(plane, {}).update(t)
    return events, table


# ------------------------------------------------------- reduction
@dataclasses.dataclass
class Spans:
    window_s: float
    busy_s: float
    spans_s: Dict[str, float]       # host span -> seconds in the window
    spans_n: Dict[str, int]         # host span -> count in the window
    idle_by_span: Dict[str, float]  # innermost span -> idle seconds
    leaf_s: Dict[Tuple[str, str], float]  # (canonical, scope) -> s/device
    gaps: List[Tuple[float, str, float]]  # longest idle gaps: (seconds,
    #                                       innermost span, start in window)
    stat_names: List[str]           # stats found on device op metadata

    def scopes_matching(self, pattern: str) -> float:
        """Device seconds of the leaf ops whose scope path matches."""
        rx = re.compile(pattern)
        return sum(s for (_, sc), s in self.leaf_s.items() if rx.search(sc))

    def ops_in_scope(self, pattern: str) -> Dict[str, float]:
        """Canonical op name -> device seconds, for leaves whose scope
        path matches ``pattern`` (``""`` matches every op)."""
        rx = re.compile(pattern)
        out: Dict[str, float] = collections.defaultdict(float)
        for (n, sc), s in self.leaf_s.items():
            if rx.search(sc):
                out[n] += s
        return dict(out)

    def unscoped(self) -> Dict[str, float]:
        """Canonical op name -> device seconds of leaves with no scope."""
        out: Dict[str, float] = collections.defaultdict(float)
        for (n, sc), s in self.leaf_s.items():
            if not sc:
                out[n] += s
        return dict(out)


def reduce(events: List[xtrace.Event], table=None,
           window_span: str = xtrace.WINDOW_SPAN,
           top_gaps: int = 10) -> Spans:
    table = table or {}
    host = [e for e in events if not e.plane.startswith("/device:")]
    win = [e for e in host if e.name == window_span]
    dev_ops = [e for e in events if e.plane.startswith("/device:")
               and e.line == xtrace.OPS_LINE]
    if not dev_ops:
        raise ValueError("the trace holds no device op")
    if win:
        t0, t1 = win[0].start_ns, win[0].end_ns
    else:
        t0 = min(e.start_ns for e in dev_ops)
        t1 = max(e.end_ns for e in dev_ops)

    def clip(e) -> Optional[Tuple[float, float]]:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        return (a, b) if b > a else None

    planes = sorted({e.plane for e in dev_ops})
    leaf_s: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    stat_names = set()
    busy, first_busy = 0.0, []
    for p in planes:
        ops = sorted((e for e in dev_ops if e.plane == p),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        meta = table.get(p, {})
        for stats in meta.values():
            stat_names.update(stats)
        iv = []
        for i, e in enumerate(ops):
            c = clip(e)
            if c is None:
                continue
            iv.append(c)
            if i + 1 < len(ops) and ops[i + 1].start_ns < e.end_ns:
                continue
            key = (xtrace.canonical(e.name), _scope(meta.get(e.name, {})))
            leaf_s[key] += (c[1] - c[0]) / 1e9 / len(planes)
        merged = xtrace._union(iv)
        busy += sum(b - a for a, b in merged) / 1e9
        if p == planes[0]:
            first_busy = merged
    spans = sorted((e for e in host if e.name.startswith(SPAN_PREFIXES)
                    and e.name != window_span),
                   key=lambda e: (e.start_ns, -e.dur_ns))
    spans_s: Dict[str, float] = collections.defaultdict(float)
    spans_n: Dict[str, int] = collections.defaultdict(int)
    for e in spans:
        if (c := clip(e)):
            spans_s[e.name] += (c[1] - c[0]) / 1e9
            spans_n[e.name] += 1
    # idle gaps in time order against the spans in start order: a stack
    # of the spans open at the gap's midpoint, innermost on top
    idle: Dict[str, float] = collections.defaultdict(float)
    gaps: List[Tuple[float, str, float]] = []
    edges = [t0] + [x for iv in first_busy for x in iv] + [t1]
    stack: List[xtrace.Event] = []
    j = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        while j < len(spans) and spans[j].start_ns <= mid:
            while stack and stack[-1].end_ns < spans[j].start_ns:
                stack.pop()
            stack.append(spans[j])
            j += 1
        while stack and stack[-1].end_ns < mid:
            stack.pop()
        name = stack[-1].name if stack else "none"
        idle[name] += (b - a) / 1e9
        gaps.append(((b - a) / 1e9, name, (a - t0) / 1e9))
    return Spans(window_s=(t1 - t0) / 1e9, busy_s=busy / len(planes),
                 spans_s=dict(spans_s), spans_n=dict(spans_n),
                 idle_by_span=dict(idle),
                 leaf_s=dict(leaf_s), stat_names=sorted(stat_names),
                 gaps=heapq.nlargest(top_gaps, gaps))
