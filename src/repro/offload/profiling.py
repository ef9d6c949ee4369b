"""Profiler-measured schedule latency: device timings into EngineTelemetry.

In sim and driver mode the engine times dispatches with a host wall clock;
inside ``shard_map`` (spmd mode) it "leaves latency to the profiler". This
module closes that loop: one dispatch runs under ``jax.profiler`` with an
annotation naming the schedule, the emitted
``*.trace.json.gz`` chrome trace is parsed with the stdlib (no tensorboard
dependency), and the *device-side execution time* of that dispatch — the
union of the device timeline's event intervals (on the CPU backend, of the
executable-run events inside the annotation window), so nested events
never double-count — is recorded into
:class:`~repro.offload.engine.EngineTelemetry` as a **measured-on-device**
latency source, distinct from the wall-clock numbers. That is the software
analogue of the paper's 8 ns on-NIC timer: the host clock sees dispatch +
transfer + sync; the trace sees the collective itself.

When the runtime cannot produce or parse a trace (a second concurrent
profiler session, a backend without the chrome-trace export), measurement
falls back to the annotation's own wall duration and is labeled
``source="wall"`` so dashboards never mistake it for a device number —
and the *reason* for the degradation is recorded
(:attr:`DeviceTiming.fallback_reason`, counted into
``EngineTelemetry.snapshot()["profiler_fallback_reasons"]`` and the
``repro_engine_profiler_fallbacks_total`` metric), so a profiler that has
silently stopped producing traces shows up on a dashboard instead of
quietly substituting wall numbers.

The annotation is a tracer span (:mod:`repro.obs.tracing`) named by the
tag, which a collecting tracer's spans hold open as a profiler annotation:
when a collecting tracer is installed the same name appears in both the
host span trace and the profiler's chrome trace, which is the anchor
:func:`repro.obs.export.merge_device_trace` uses to align the two clocks
into one host+device timeline.
"""

from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import tempfile
import time
from typing import Any, List, Optional, Tuple

import jax

PyTree = Any

#: every annotation this module emits starts with this prefix
ANNOTATION_PREFIX = "repro_offload"

#: trace event names that mark device-side executable execution. CPU runs
#: emit TfrtCpuExecutable events; GPU/TPU runs emit XlaModule/stream events.
_DEVICE_EVENT_RE = re.compile(
    r"Executable::Execute|ExecuteHelper|XlaModule|ExecutorExecute"
)


@dataclasses.dataclass(frozen=True)
class DeviceTiming:
    """One profiled dispatch: where each number came from."""

    coll: str
    device_us: float       # union of device-exec intervals in the window
    wall_us: float         # host wall clock around the same dispatch
    source: str            # "profiler" (trace-derived) or "wall" (fallback)
    events: int            # device-exec events attributed to the window
    trace_path: Optional[str] = None
    #: why source degraded to "wall": "trace_start_failed" (most often a
    #: concurrent profiler session), "stop_failed", "no_trace_file", or
    #: "parse_failed"; None when the profiler delivered
    fallback_reason: Optional[str] = None


def _newest_trace_file(trace_dir: str) -> Optional[str]:
    paths = glob.glob(
        os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True
    )
    return max(paths, key=os.path.getmtime) if paths else None


def _interval_union_us(intervals: List[Tuple[float, float]]) -> float:
    total = 0.0
    end = -1.0
    for lo, hi in sorted(intervals):
        if lo > end:
            total += hi - lo
            end = hi
        elif hi > end:
            total += hi - end
            end = hi
    return total


def parse_device_us(
    trace_path: str, annotation: str
) -> Optional[Tuple[float, int]]:
    """(device µs, event count) for the one dispatch a trace holds, or None.

    Reads the chrome-trace JSON jax writes next to its xplane protobuf.
    Device time is an interval union, so nested events count once.

    * An accelerator trace has a ``/device:...`` process, the device's own
      timeline. Every event on it counts, unclipped: the session brackets
      exactly one dispatch (:func:`profile_offload` blocks on its inputs
      and warmup before starting it), and the device clock is aligned to
      the host's only approximately, so clipping to the host annotation
      could drop the whole dispatch.
    * A trace without one (the CPU backend) times the host's
      executable-execution events inside the annotation's window.
    """
    try:
        trace = json.loads(gzip.open(trace_path, "rb").read())
    except (OSError, ValueError):
        return None
    events = trace.get("traceEvents", [])
    device_pids = {
        e.get("pid")
        for e in events
        if e.get("ph") == "M"
        and e.get("name") == "process_name"
        and str(e.get("args", {}).get("name", "")).startswith("/device:")
    }
    spans = [e for e in events if e.get("ph") == "X"]
    if device_pids:
        chosen = [e for e in spans if e.get("pid") in device_pids]
        lo_w, hi_w = float("-inf"), float("inf")
    else:
        window = next((e for e in spans if e.get("name") == annotation), None)
        if window is None:
            return None
        lo_w = float(window.get("ts", 0.0))
        hi_w = lo_w + float(window.get("dur", 0.0))
        chosen = [
            e for e in spans
            if _DEVICE_EVENT_RE.search(str(e.get("name", "")))
        ]
    intervals: List[Tuple[float, float]] = []
    for e in chosen:
        lo = float(e.get("ts", 0.0))
        hi = lo + float(e.get("dur", 0.0))
        lo, hi = max(lo, lo_w), min(hi, hi_w)
        if hi > lo:
            intervals.append((lo, hi))
    if not intervals:
        return None
    return _interval_union_us(intervals), len(intervals)


def profile_offload(
    engine,
    descriptor,
    x: Optional[PyTree] = None,
    *,
    axis_name=None,
    mesh=None,
    warmup: int = 1,
    trace_dir: Optional[str] = None,
) -> DeviceTiming:
    """Dispatch one descriptor under a profiler trace; feed the telemetry.

    Works in sim mode and in driver mode (both are host-dispatched: the
    engine owns the program, so the trace brackets exactly one schedule).
    ``warmup`` dispatches first so compilation never pollutes the window.
    The measurement lands in ``engine.telemetry`` via
    ``record_device_latency`` and is what puts a measured-on-device source
    behind ``latency_by_coll_us`` in ``EngineTelemetry.snapshot()``.
    """
    from repro.obs import tracing as obs_tracing

    desc = engine._as_descriptor(descriptor)
    coll = desc.coll_type.name.lower()
    # the session must hold this one dispatch alone: nothing earlier may
    # still be running on the device when it starts
    jax.block_until_ready(x)
    for _ in range(max(0, warmup)):
        jax.block_until_ready(
            engine.offload(desc, x, axis_name=axis_name, mesh=mesh)
        )
    tag = f"{ANNOTATION_PREFIX}:{coll}:p{desc.comm_size}"
    owned = trace_dir is None
    tmp = tempfile.mkdtemp(prefix="repro_prof_") if owned else trace_dir
    parsed: Optional[Tuple[float, int]] = None
    trace_path: Optional[str] = None
    fallback_reason: Optional[str] = None
    # the tag's span is the trace's annotation and, in the active tracer,
    # the host span merge_device_trace aligns the clocks on; with tracing
    # off a private tracer still makes the annotation
    span_tracer = obs_tracing.get_tracer()
    if not span_tracer.enabled:
        span_tracer = obs_tracing.Tracer()
    try:
        # trace machinery failures (a concurrent profiler session, a
        # backend without the chrome export) degrade to the wall-clock
        # source — but a failing DISPATCH always propagates
        try:
            jax.profiler.start_trace(tmp)
            tracing = True
        except Exception:
            tracing = False
            fallback_reason = "trace_start_failed"
        t0 = time.perf_counter()
        try:
            with span_tracer.span(tag, "profile", coll=coll, annotation=True):
                out = engine.offload(desc, x, axis_name=axis_name, mesh=mesh)
                jax.tree.map(lambda a: a.block_until_ready(), out)
        finally:
            wall_us = (time.perf_counter() - t0) * 1e6
            if tracing:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    tracing = False
                    fallback_reason = "stop_failed"
        if tracing:
            try:
                trace_path = _newest_trace_file(tmp)
                if trace_path is None:
                    fallback_reason = "no_trace_file"
                else:
                    parsed = parse_device_us(trace_path, tag)
                    if parsed is None:
                        fallback_reason = "parse_failed"
            except Exception:
                parsed = None
                fallback_reason = "parse_failed"
    finally:
        if owned:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)
            trace_path = None
    if parsed is not None:
        device_us, n_events = parsed
        source = "profiler"
        fallback_reason = None
    else:
        device_us, n_events = wall_us, 0
        source = "wall"
        if fallback_reason is None:
            fallback_reason = "trace_start_failed"
        record = getattr(
            engine.telemetry, "record_profiler_fallback", None
        )
        if record is not None:
            record(coll, fallback_reason)
    engine.telemetry.record_device_latency(
        coll, device_us * 1e-6, source=source
    )
    return DeviceTiming(
        coll=coll,
        device_us=device_us,
        wall_us=wall_us,
        source=source,
        events=n_events,
        trace_path=trace_path,
        fallback_reason=fallback_reason,
    )


__all__ = [
    "ANNOTATION_PREFIX",
    "DeviceTiming",
    "parse_device_us",
    "profile_offload",
]
