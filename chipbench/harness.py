"""One run of one cell: set-up, the measured window, the trace, the check.

The end-to-end metrics are taken here, by the host clock, over all the work
and all the time of the window; the per-layer metrics come from the readers
in ``metrics/`` in a ``--trace 1`` run. The last line of standard output is
the result; the last lines of standard error are the numbers compared, each
beside its limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from chipbench import spec

#: run-time files: traces, the broker's registry, tuning caches (.gitignore)
SCRATCH = spec.ROOT / ".chipbench"
#: JAX's persistent compile cache, at a fixed path in the checkout
CACHE_DIR = spec.ROOT / ".jax_cache"


def prepare_env() -> None:
    """Before JAX is imported: the compile cache in the checkout, every
    program cached, and no ambient tuning table, registry or tracer."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # no eviction: it reads a timestamp file beside every entry, and one
    # missing (an entry copied in without it) fails every later write
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["REPRO_CACHE_DIR"] = str(SCRATCH / "repro_cache")
    os.environ["TPU_LOG_DIR"] = str(SCRATCH / "tpu_logs")
    for var in ("REPRO_TUNING_TABLE", "REPRO_TUNING_REGISTRY", "REPRO_TRACE",
                "REPRO_FLIGHT_RECORD"):
        os.environ.pop(var, None)


def percentile(values: List[float], q: float) -> Optional[float]:
    import numpy as np

    return float(np.percentile(np.asarray(values), q)) if len(values) else None


#: end-to-end metrics, each over the whole window of one run
E2E = {
    "setup_s": lambda r: r.setup_s,
    "latency_p50_us": lambda r: _us(percentile(r.window.latencies_s, 50)),
    "latency_p95_us": lambda r: _us(percentile(r.window.latencies_s, 95)),
    "served_req_per_s": lambda r: r.window.correct_in_window / r.seconds,
    "train_tokens_per_s": lambda r: r.window.tokens / r.window.seconds,
}


def _us(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else seconds * 1e6


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""

    cell: Dict[str, Any]
    workload: Dict[str, Any]
    config: Dict[str, Any]
    peaks: Dict[str, Any]
    seconds: float
    setup_s: float
    window: Any
    counters_before: Dict[str, Any]
    counters_after: Dict[str, Any]
    spans: Optional[list] = None
    reduction: Any = None
    trace_window_s: Optional[float] = None
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)

    def counter_delta(self, group: str, name: str) -> Optional[float]:
        try:
            return self.counters_after[group][name] - self.counters_before[group][name]
        except KeyError:
            return None


class GcPauses:
    """Collector pauses while armed: how often and how long the interpreter
    stopped to collect (info only; a pause stalls every thread)."""

    def __init__(self) -> None:
        self.armed = False
        self.pauses: List[float] = []
        self._t0 = 0.0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if not self.armed:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t0)

    def summary(self) -> Dict[str, float]:
        gc.callbacks.remove(self._on_gc)
        return {"count": len(self.pauses), "max_ms": max(self.pauses, default=0.0) * 1e3,
                "total_ms": sum(self.pauses) * 1e3}


class CompileCounter:
    """XLA compiles and persistent-cache loads, from JAX's own events."""

    def __init__(self) -> None:
        import jax

        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event: str, *_a, **_k) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event: str, *_a, **_k) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @property
    def count(self) -> int:
        return self.compiles + self.cache_hits


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="compare the control in the program's place: the "
                         "result line then reads correct false")
    return ap.parse_args(argv)


def find_chips(chips: int):
    """The cell's chips, or None (with the reason on stderr)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chipbench: no TPU found (JAX sees {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return None
    if len(devs) < chips:
        print(f"chipbench: the cell needs {chips} TPU chips, found "
              f"{len(devs)}; nothing was run", file=sys.stderr)
        return None
    return devs[:chips]


def main(argv=None, *, t_start: float) -> int:
    args = parse_args(argv)
    prepare_env()
    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    import jax  # noqa: F401 - after prepare_env

    devices = find_chips(int(cell["chips"]))
    if devices is None:
        return 2
    sys.path.insert(0, str(spec.ROOT / "src"))
    result = run_cell(
        bench, cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), devices=devices, t_start=t_start,
        control=args.control,
    )
    return 0 if result is not None else 1


def run_cell(
    bench: Dict[str, Any], cell: Dict[str, Any], *, seed: int, seconds: float,
    trace: bool, devices, t_start: float, control: bool = False,
    base: Path = spec.BENCH_DIR,
    workload: Optional[Dict[str, Any]] = None,
    config: Optional[Dict[str, Any]] = None, peaks_of: Any = None,
) -> Optional[Dict[str, Any]]:
    """Run one cell and print its result line; returns the result. With
    ``control`` the control stands in the program's place in the comparison.

    ``workload``/``config``/``peaks_of`` replace the cell's files and the
    peaks table (tests shrink the cell and run it on the CPU; the knee sweep
    offers other rates)."""
    from chipbench import peaks as peaks_mod
    from chipbench import systems

    started_s = time.perf_counter() - t_start
    workload = workload or spec.load_workload(cell["name"], base)
    config = config or spec.load_config(workload["config"], base)
    kind = devices[0].device_kind
    peak = (peaks_of or peaks_mod.for_device)(kind)
    SCRATCH.mkdir(parents=True, exist_ok=True)
    system = systems.build(
        config, workload, seed=seed, seconds=seconds, devices=devices,
        scratch=SCRATCH, tracing=trace,
    )
    compiles = CompileCounter()
    system.setup()
    # the set-up's own objects (payloads, schedules) are not the window's
    # garbage: keep the collector from walking them inside the window
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    before = system.counters()
    compiles_before = compiles.count
    trace_dir = SCRATCH / "trace" / cell["name"]
    if trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # tracing every Python call stalls the host path
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    t_trace = time.perf_counter()
    gc_pauses = GcPauses()
    gc_pauses.armed = True
    window = system.window()
    gc_pauses.armed = False
    reduction = trace_window_s = None
    if trace:
        import jax

        trace_window_s = time.perf_counter() - t_trace
        jax.profiler.stop_trace()
    compiles_in_window = compiles.count - compiles_before
    setup_compiles = {"compiled": compiles.compiles, "cache_hits": compiles.cache_hits}
    after = system.counters()
    spans = system.spans() if trace else None
    memory_peak = systems.peak_bytes(devices)
    if trace:
        from chipbench import tracereduce

        path = tracereduce.newest_trace_file(str(trace_dir))
        reduction = tracereduce.reduce_file(path) if path else None
        shutil.rmtree(trace_dir, ignore_errors=True)
    system.release()
    gc.unfreeze()
    gc.collect()
    checks = system.check(control=control)

    run = Run(
        cell=cell, workload=workload, config=config, peaks=peak,
        seconds=seconds, setup_s=setup_s, window=window,
        counters_before=before, counters_after=after, spans=spans,
        reduction=reduction, trace_window_s=trace_window_s,
    )
    e2e_entries, layer_entries = spec.cell_metrics(bench, cell["name"])
    for m in e2e_entries:
        value = E2E[m["name"]](run)
        if value is not None:
            run.e2e[m["name"]] = value
    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        for m in layer_entries:
            value = spec.load_reader(m["name"], base)(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e_entries:
            if m["name"] in run.e2e:
                metrics[m["name"]] = {"value": run.e2e[m["name"]], "unit": m["unit"]}

    device: Dict[str, Any] = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }
    out: Dict[str, Any] = {
        "correct": all(c.ok for c in checks),
        "attempted": window.attempted,
        "failed": window.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and reduction is not None:
        device["busy_s"] = reduction.mean_busy_s()
        device["window_s"] = trace_window_s
        out["breakdown"] = {
            "device_ops": reduction.top_ops(10),
            "idle_gaps": reduction.idle_gaps(10),
        }
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}

    info = dict(window.info)
    if control:
        info["control"] = info.get("control", True)
    info.update(
        process_to_chips_s=started_s,
        setup_compiles=setup_compiles,
        gc_pauses_in_window=gc_pauses.summary(),
        xla_compiles_in_window=compiles_in_window,
        window_s=window.seconds, e2e=run.e2e,
    )
    if reduction is not None:
        info["device_lines"] = [
            {"device": d.name, "busy_us": d.busy_us, "events": d.events,
             "lines": d.lines}
            for d in reduction.devices
        ]
    print(json.dumps({"info": info}), flush=True)
    print(json.dumps(out), flush=True)
    for c in checks:
        print(f"check {c.name}: {c.value} (limit {c.limit})", file=sys.stderr)
    return out
