"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time and idle share over the traced
window, device time by op and by executable, and idle gaps attributed to
the harness span the host was in.

The window is the host span ``bench.window`` that the harness opens
around its measured loop. Device planes are those named ``/device:...``
that carry an ``XLA Ops`` line; busy time is the union of their op
intervals inside the window, averaged over the devices that ran any.
Executables are the events of the ``XLA Modules`` line (``jit_<fn>``
names; a jit of a ``functools.partial`` shows as ``jit__unknown``).

On a TPU the ``XLA Ops`` line names each op by its HLO text and nests a
loop's body ops inside the loop's own event. The reduction keeps the
leaves (ops that contain no other op) and names each by its HLO name,
result type and op kind, layouts and operands dropped
(``canonical``). Pallas kernels appear as ``custom-call`` ops with no
kernel name, so a metric finds its kernel by the kernel's result type.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def canonical(name: str) -> str:
    """``%closed_call.5 = (bf16[8,32,1024]{2,1,0:T(8,128)}, ...)
    custom-call(s32[8,64] %a, ...), custom_call_target=...`` ->
    ``%closed_call.5 = (bf16[8,32,1024], ...) custom-call``."""
    if " = " not in name:
        return name
    n = re.sub(r"/\*[^*]*\*/", "", name)
    while True:
        m = re.sub(r"\{[^{}]*\}", "", n)
        if m == n:
            break
        n = m
    head, _, rest = n.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        result, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        result, _, rest = rest.partition(" ")
    kind = re.match(r"[\w.-]*", rest).group(0)
    return re.sub(r"\s+", " ", f"{head} = {result} {kind}")


def events_from_profile(pd) -> List[Event]:
    """Flatten a ``jax.profiler.ProfileData`` into events."""
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns)))
    return out


def load_dir(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for p in sorted(paths):
        events += events_from_profile(ProfileData.from_file(p))
    return events


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    op_s: Dict[str, float]          # device op name -> seconds (mean/device)
    module_s: Dict[str, float]      # executable name -> seconds (mean/device)
    idle_by_span: Dict[str, float]  # host span -> idle device seconds
    spans_s: Dict[str, float]       # host span -> seconds inside the window
    modules: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)       # (name, start_ns, end_ns), first device
    leaves: List[Tuple[float, float, str]] = dataclasses.field(
        default_factory=list)       # (start_ns, end_ns, canonical name)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def ops_matching(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s.items() if rx.search(n))

    def modules_matching(self, pattern: str,
                         containing: Optional[str] = None) -> float:
        """Seconds of the executables named by ``pattern`` (first
        device); with ``containing``, only those that ran an op whose
        canonical name matches it."""
        rx = re.compile(pattern)
        mods = [m for m in self.modules if rx.search(m[0])]
        if containing is None:
            return sum(b - a for _, a, b in mods) / 1e9
        cx = re.compile(containing)
        starts = [a for a, _, n in self.leaves if cx.search(n)]
        total = 0.0
        for _, a, b in mods:
            i = bisect.bisect_left(starts, a)
            if i < len(starts) and starts[i] < b:
                total += b - a
        return total / 1e9

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(events: List[Event], window_span: str = WINDOW_SPAN) -> Reduced:
    host = [e for e in events if not e.plane.startswith("/device:")]
    win = [e for e in host if e.name == window_span]
    dev_ops = [e for e in events
               if e.plane.startswith("/device:") and e.line == OPS_LINE]
    if not dev_ops:
        raise ValueError("the trace holds no device op")
    if win:
        t0, t1 = win[0].start_ns, win[0].end_ns
    else:
        t0 = min(e.start_ns for e in dev_ops)
        t1 = max(e.end_ns for e in dev_ops)

    def clip(e: Event) -> Optional[Tuple[float, float]]:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        return (a, b) if b > a else None

    planes = sorted({e.plane for e in dev_ops})
    op_s: Dict[str, float] = collections.defaultdict(float)
    module_s: Dict[str, float] = collections.defaultdict(float)
    busy = 0.0
    first_busy: List[List[float]] = []
    leaves: List[Tuple[float, float, str]] = []
    names: Dict[str, str] = {}
    for p in planes:
        ops = sorted((e for e in dev_ops if e.plane == p),
                     key=lambda e: (e.start_ns, -e.dur_ns))
        iv = []
        for i, e in enumerate(ops):
            c = clip(e)
            if c is None:
                continue
            iv.append(c)
            # a leaf: the next op (in start order) does not start inside
            if i + 1 < len(ops) and ops[i + 1].start_ns < e.end_ns:
                continue
            name = names.get(e.name)
            if name is None:
                name = names[e.name] = canonical(e.name)
            op_s[name] += (c[1] - c[0]) / 1e9 / len(planes)
            if p == planes[0]:
                leaves.append((c[0], c[1], name))
        merged = _union(iv)
        busy += sum(b - a for a, b in merged) / 1e9
        if p == planes[0]:
            first_busy = merged
    modules = []
    for e in events:
        if e.plane in planes and e.line == MODULES_LINE and (c := clip(e)):
            module_s[e.name] += (c[1] - c[0]) / 1e9 / len(planes)
            if e.plane == planes[0]:
                modules.append((e.name, c[0], c[1]))
    spans = [e for e in host if e.name.startswith(SPAN_PREFIX)
             and e.name != window_span]
    spans_s: Dict[str, float] = collections.defaultdict(float)
    for e in spans:
        if (c := clip(e)):
            spans_s[e.name] += (c[1] - c[0]) / 1e9
    # idle gaps of the first device, each charged to the innermost
    # harness span around its midpoint
    idle: Dict[str, float] = collections.defaultdict(float)
    spans.sort(key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    edges = [t0] + [x for iv in first_busy for x in iv] + [t1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        i = bisect.bisect_right(starts, mid)
        # harness spans nest shallowly: look at the few that start last
        around = [e for e in spans[max(0, i - 4):i] if e.end_ns >= mid]
        name = min(around, key=lambda e: e.dur_ns).name if around else "none"
        idle[name] += (b - a) / 1e9
    return Reduced(window_s=(t1 - t0) / 1e9, busy_s=busy / len(planes),
                   n_devices=len(planes), op_s=dict(op_s),
                   module_s=dict(module_s), idle_by_span=dict(idle),
                   spans_s=dict(spans_s), modules=modules, leaves=leaves)
