"""Leaf device ops inside the K-step decode scans of a reduced trace
(xtrace.Reduced), for readers of a kernel that runs in several
executables (prefill, the scans, ...). The scans are ServeEngine's
``_macro_fn`` jits, found as decode_step_ms.decode finds them: the full
variant shows as ``jit__macro_fn``, the ``simple`` one as
``jit__unknown``, told apart from the map's other partial jits by
running the paged-attention kernel."""
from __future__ import annotations

import bisect
import re
from typing import List, Tuple

SCAN = re.compile(r"^jit__(macro_fn|unknown)\(")
# the paged-attention Pallas kernel: out, and its (m, l) stats [B, H, 1]
PA_KERNEL = re.compile(
    r"= \(bf16\[\d+,\d+,\d+\], f32\[\d+,\d+,1\], f32\[\d+,\d+,1\]\) custom-call$")


def matching(trace, pattern) -> List[Tuple[float, "re.Match"]]:
    """(seconds, match) of each leaf (first device) whose canonical name
    matches ``pattern`` and that starts inside a decode-scan executable."""
    rx = re.compile(pattern)
    leaves = trace.leaves
    starts = [a for a, _, n in leaves if PA_KERNEL.search(n)]
    scans = []
    for name, a, b in trace.modules:
        if SCAN.search(name):
            i = bisect.bisect_left(starts, a)
            if i < len(starts) and starts[i] < b:
                scans.append((a, b))
    scans.sort()
    begins = [a for a, _ in scans]
    out = []
    for a, b, name in leaves:
        m = rx.search(name)
        if m is None:
            continue
        i = bisect.bisect_right(begins, a) - 1
        if i >= 0 and a < scans[i][1]:
            out.append(((b - a) / 1e9, m))
    return out
