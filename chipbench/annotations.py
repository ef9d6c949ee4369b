"""The program's spans on the device's timeline.

A collecting tracer's context spans are host events of the profiler trace
(``TraceReduction.host``), on the profiler's host clock. The first
profiler session of a process has the device's clock off the host's (about
-1.3 ms on a TPU v5e, PERF.md), so the device timeline is first moved onto
the host clock by an offset fitted from the engine's launches: the shift at
which the most ``engine.launch`` events see the device's next busy
interval start inside them (a launched schedule starts on an idle device
before its launch returns, and never before the launch begins).
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from chipbench.tracereduce import merged

Interval = Tuple[float, float]

#: offsets searched, us either way: past any first-session offset seen
SEARCH_US = 5000.0
STEP_US = 10.0


def overlap_us(a: Sequence[Interval], b: Sequence[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def launch_offset_us(host, busy: Sequence[Interval]) -> float:
    """Device clock minus host clock, in us, fitted from the pairs of each
    ``engine.launch`` and the device's next busy interval; 0 without
    launches. Of the first run of shifts that pair the most launches, the
    largest is taken: then no paired schedule starts before its launch,
    and the one that starts soonest starts as it is launched."""
    launches = np.array([(lo, hi) for n, lo, hi in host if n == "engine.launch"])
    if not len(launches) or not len(busy):
        return 0.0
    starts = np.array([lo for lo, _ in busy])

    def hits(shift: float) -> int:
        k = np.searchsorted(starts, launches[:, 0] + shift)
        inside = k < len(starts)
        nxt = starts[np.minimum(k, len(starts) - 1)]
        return int((inside & (nxt <= launches[:, 1] + shift)).sum())

    grid = np.arange(-SEARCH_US, SEARCH_US + 1.0, STEP_US)
    score = np.array([hits(s) for s in grid])
    end = int(np.argmax(score))
    while end + 1 < len(grid) and score[end + 1] == score[end]:
        end += 1
    return float(grid[end])


def device_idle_under(run, name: str) -> Optional[float]:
    """Share of the traced window, in %, in which the busiest chip is idle
    (between two of its busy intervals) while a ``name`` span is open on
    the host; None where the trace holds no such span."""
    red = run.reduction
    dev = red.busiest() if red is not None else None
    if dev is None or not run.trace_window_s:
        return None
    under = merged([(lo, hi) for n, lo, hi in red.host if n == name])
    if not under:
        return None
    shift = launch_offset_us(red.host, dev.busy)
    gaps: List[Interval] = [
        (a[1] - shift, b[0] - shift)
        for a, b in zip(dev.busy, dev.busy[1:]) if b[0] > a[1]
    ]
    return 100.0 * overlap_us(under, gaps) / (run.trace_window_s * 1e6)
