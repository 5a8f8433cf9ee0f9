"""Structured counter registry for host-side instrumentation.

The serving stack grew a handful of module-level mutable-list counters
(``XLATE_CALLS = [0]`` in kv_manager, ``PROBE_TRACES``/``INSERT_TRACES``
in the fused map layer, ``MACRO_DISPATCHES``/``HOST_SYNCS`` in the
engine). Each is a one-element list so call sites can bump shared state
without ``global``; tests snapshot them by hand with ad-hoc
``before = X[0]`` bookkeeping. This module keeps the cheap mutable-cell
representation — a cell IS still a one-element list, and the historical
module-level names are re-bound to the very same list objects, so every
existing ``NAME[0]`` read or ``NAME[0] += 1`` bump keeps working — but
hangs every cell off one registry with ``snapshot()/reset()/delta()``
so contract tests and the bench can treat "all counters" as a value.

Counters are host-only instrumentation: nothing here ever enters a
traced graph, and trace-time counters (``fmmu.probe_traces``) count
*tracings*, not executions, exactly as before.

``span(name, **args)`` is the matching host span: a profiler
``TraceAnnotation``, so a program's spans land in the profiler's own
host plane, on the clock of the device ops, with ``args`` as the
event's stats. With no profiler running a span costs one cheap call.
"""
from __future__ import annotations

from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation


class Counters:
    """A named registry of mutable integer cells.

    ``cell(name)`` returns the underlying one-element list itself (not a
    copy) — aliasing it to a module-level name preserves the legacy
    ``NAME[0] += 1`` idiom at zero cost while keeping the cell
    enumerable through the registry.
    """

    def __init__(self) -> None:
        self._cells: Dict[str, List[int]] = {}

    def cell(self, name: str) -> List[int]:
        """Get (or create at 0) the mutable cell for ``name``."""
        return self._cells.setdefault(name, [0])

    def snapshot(self) -> Dict[str, int]:
        """Current value of every registered counter, as plain ints."""
        return {k: int(v[0]) for k, v in self._cells.items()}

    def reset(self, name: Optional[str] = None) -> None:
        """Zero one counter (or all of them when ``name`` is None).

        Resets mutate the existing cells in place — aliases stay valid.
        """
        if name is not None:
            self.cell(name)[0] = 0
            return
        for v in self._cells.values():
            v[0] = 0

    def delta(self, base: Dict[str, int]) -> Dict[str, int]:
        """Per-counter change since a prior ``snapshot()``.

        Counters created after the base snapshot report their full
        current value (base 0).
        """
        return {k: int(v[0]) - int(base.get(k, 0))
                for k, v in self._cells.items()}


# The process-wide registry. Subsystems register their cells at import
# time (`X = COUNTERS.cell("sub.x")`) and keep bumping `X[0]` as before.
COUNTERS = Counters()


def span(name: str, **args):
    """A host span ``name`` (a context manager) recorded by the profiler
    with ``args`` as its stats. A step takes a plain annotation too: on a
    TPU v5e, two of three traced serving runs whose steps were
    ``StepTraceAnnotation``s stalled ~4.5 s inside one readback, and two
    runs of the same traffic with plain annotations did not."""
    return TraceAnnotation(name, **args)
