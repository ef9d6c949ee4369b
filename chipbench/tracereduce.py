"""Reduction of a ``jax.profiler`` trace to device busy time.

Reads the Chrome-trace JSON (``*.trace.json.gz``) that ``jax.profiler``
writes beside its xplane file. Each ``/device:...`` process is one chip's
own timeline; its busy time is the union of its events' intervals, so
nested events (a module and the ops inside it) count once. The ``Steps``
line marks step boundaries, not work, and is left out. The timeline is
read whole: the first profiler session of a process has its device clock
offset from the host's by up to about a millisecond, so clipping device
events to a host window could drop them.

Idle gaps are attributed to what the host was doing: the host event that
overlaps the gap most (the shortest on a tie).
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

#: device lines that mark spans of time rather than work done in them
MARKER_LINES = frozenset({"Steps"})


def union_us(intervals: Sequence[Interval]) -> float:
    total, end = 0.0, float("-inf")
    for lo, hi in sorted(intervals):
        if lo > end:
            total += hi - lo
            end = hi
        elif hi > end:
            total += hi - end
            end = hi
    return total


def merged(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class DeviceTimeline:
    name: str
    busy_us: float
    events: int
    op_us: Dict[str, float]
    busy: List[Interval]
    #: events per named line (thread) of the timeline
    lines: Dict[str, int] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class TraceReduction:
    devices: List[DeviceTimeline]
    host: List[Tuple[str, float, float]]  # (name, start_us, end_us)

    def busiest(self) -> Optional[DeviceTimeline]:
        return max(self.devices, key=lambda d: d.busy_us, default=None)

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(d.busy_us for d in self.devices) / len(self.devices) / 1e6

    def top_ops(self, k: int = 10) -> List[List]:
        dev = self.busiest()
        if dev is None:
            return []
        ops = sorted(dev.op_us.items(), key=lambda kv: -kv[1])[:k]
        return [[name, us / 1e6] for name, us in ops]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps between busy intervals of the busiest chip,
        each named by the host event that overlaps it most."""
        dev = self.busiest()
        if dev is None or len(dev.busy) < 2:
            return []
        gaps = [
            (b[0] - a[1], a[1], b[0])
            for a, b in zip(dev.busy, dev.busy[1:])
            if b[0] > a[1]
        ]
        gaps.sort(key=lambda g: -g[0])
        out = []
        for length, lo, hi in gaps[:k]:
            best, key = "no host event", (0.0, 0.0)
            for name, hlo, hhi in self.host:
                ov = min(hi, hhi) - max(lo, hlo)
                if ov > 0 and (ov, -(hhi - hlo)) > key:
                    best, key = name, (ov, -(hhi - hlo))
            out.append([best, length / 1e6])
        return out


def newest_trace_file(trace_dir: str) -> Optional[str]:
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_events(events: Sequence[dict]) -> TraceReduction:
    procs: Dict[object, str] = {}
    threads: Dict[Tuple[object, object], str] = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        name = str(e.get("args", {}).get("name", ""))
        if e.get("name") == "process_name":
            procs[e.get("pid")] = name
        elif e.get("name") == "thread_name":
            threads[(e.get("pid"), e.get("tid"))] = name
    device_pids = sorted(
        (pid for pid, name in procs.items() if name.startswith("/device:")),
        key=lambda pid: procs[pid],
    )
    spans: Dict[object, List[dict]] = {pid: [] for pid in device_pids}
    host: List[Tuple[str, float, float]] = []
    for e in events:
        if e.get("ph") != "X":
            continue
        lo = float(e.get("ts", 0.0))
        hi = lo + float(e.get("dur", 0.0))
        if e.get("pid") in spans:
            spans[e["pid"]].append(e)
        elif hi > lo:
            host.append((str(e.get("name", "")), lo, hi))
    devices = []
    for pid in device_pids:
        intervals: List[Interval] = []
        op_us: Dict[str, float] = {}
        lines: Dict[str, int] = {}
        for e in spans[pid]:
            lo = float(e.get("ts", 0.0))
            hi = lo + float(e.get("dur", 0.0))
            if hi <= lo:
                continue
            line = threads.get((pid, e.get("tid")), "")
            lines[line] = lines.get(line, 0) + 1
            if line in MARKER_LINES:
                continue
            intervals.append((lo, hi))
            if "op" in line.lower():
                name = str(e.get("name", ""))
                op_us[name] = op_us.get(name, 0.0) + (hi - lo)
        devices.append(DeviceTimeline(
            name=procs[pid], busy_us=union_us(intervals),
            events=len(intervals), op_us=op_us, busy=merged(intervals),
            lines=lines,
        ))
    return TraceReduction(devices=devices, host=host)


def reduce_file(path: str) -> TraceReduction:
    with gzip.open(path, "rb") as f:
        trace = json.loads(f.read())
    return reduce_events(trace.get("traceEvents", []))
