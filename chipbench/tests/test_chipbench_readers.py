"""CPU tests of the readers of the program's own spans: host work per
broker group, engine launch time, rounds per dispatch, and the device's
idle time under the broker's flush wait and host work, with the device's
clock moved onto the host's. Each reads a synthetic run, and reads nothing
from a program without those spans."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import annotations, harness, spec, tracereduce  # noqa: E402
from repro.obs.tracing import Span  # noqa: E402

NEW = ("broker_host_us_p50", "engine_launch_us_p50", "planner_rounds",
       "device_idle_share.flush_wait", "device_idle_share.broker_host")


def _run(spans=None, reduction=None, window_s=None):
    return harness.Run(
        cell={}, workload={}, config={}, peaks={}, seconds=1.0, setup_s=0.0,
        window=None, counters_before={}, counters_after={}, spans=spans,
        reduction=reduction, trace_window_s=window_s,
    )


def _read(metric, run):
    return spec.load_reader(metric)(run)


def _group(ids, t0, dur, wait, launch, rounds, stacked=False):
    """One broker group: dispatch_group > engine.offload > launch, wait."""
    g, o, lch, w = (next(ids) for _ in range(4))
    spans = [
        Span("broker.dispatch_group", "broker", t0, dur, g),
        Span("engine.offload", "engine", t0 + 10, launch + wait + 20, o, g,
             args={"rounds": rounds}),
        Span("engine.launch", "engine", t0 + 15, launch, lch, o),
        Span("engine.device_wait", "engine", t0 + 15 + launch, wait, w, o),
    ]
    if stacked:
        spans.append(Span("broker.stack", "broker", t0 + 1, 5.0, next(ids), g))
    return spans


def test_span_readers_read_host_work_launches_and_rounds():
    ids = iter(range(1, 100))
    spans = (
        _group(ids, 0.0, 1000.0, 300.0, 200.0, 3, stacked=True)
        + _group(ids, 5000.0, 800.0, 500.0, 100.0, 4)
        + _group(ids, 9000.0, 3000.0, 400.0, 900.0, 3)
    )
    run = _run(spans)
    # host work: 1000-300, 800-500, 3000-400 -> median 700
    assert _read("broker_host_us_p50", run) == pytest.approx(700.0)
    assert _read("engine_launch_us_p50", run) == pytest.approx(200.0)
    assert _read("planner_rounds", run) == pytest.approx(10 / 3)


def test_span_readers_read_nothing_from_a_program_without_the_spans():
    # the spans a program without the host-path split records
    spans = [
        Span("broker.dispatch_group", "broker", 0.0, 900.0, 1),
        Span("engine.offload", "engine", 10.0, 800.0, 2, 1, args={"p": 8}),
        Span("broker.queue_wait", "broker", -50.0, 50.0, 3),
    ]
    for run in (_run(spans), _run(None), _run([])):
        for m in NEW:
            assert _read(m, run) is None, m


def _reduction(shift_us):
    """Three groups on the host clock; the device's clock reads
    ``shift_us`` off it. Each group: flush wait, then a dispatch whose
    schedule runs from 50 us into its launch, and 100 us of device slices
    after its device wait."""
    host, busy = [], []
    for t in (10_000.0, 20_000.0, 30_000.0):
        host += [
            ("broker.idle", t - 6000, t - 2000),
            ("broker.flush_wait", t - 2000, t),
            ("broker.dispatch_group", t, t + 1000),
            ("engine.launch", t + 200, t + 500),
            ("engine.device_wait", t + 500, t + 700),
            ("PjitFunction(<lambda>)", t + 200, t + 500),
        ]
        busy += [(t + 250, t + 650), (t + 750, t + 850)]
    busy = [(lo + shift_us, hi + shift_us) for lo, hi in busy]
    dev = tracereduce.DeviceTimeline(
        name="/device:TPU:0", busy_us=tracereduce.union_us(busy),
        events=len(busy), op_us={}, busy=busy,
    )
    return tracereduce.TraceReduction(devices=[dev], host=host)


@pytest.mark.parametrize("shift_us", [0.0, -1300.0, 700.0])
def test_idle_shares_under_flush_wait_and_host_work(shift_us):
    red = _reduction(shift_us)
    # the fit moves each schedule to start as it is launched: 50 us early
    assert annotations.launch_offset_us(red.host, red.devices[0].busy) == \
        pytest.approx(shift_us + 50.0, abs=10.0)
    run = _run(reduction=red, window_s=0.04)
    flush = _read("device_idle_share.flush_wait", run)
    host = _read("device_idle_share.broker_host", run)
    # the two gaps between groups lie under a flush wait (2000 us each)
    # and under host work: 200 us before each later group's schedule,
    # 100 us between each schedule and its slices, 200 us after them
    assert flush == pytest.approx(100 * 4000 / 40_000, abs=0.03)
    assert host == pytest.approx(100 * (2 * 200 + 3 * 100 + 2 * 200) / 40_000,
                                 abs=0.03)
    collective = _read("device_idle_share.collective", run)
    assert flush + host <= collective


def test_idle_shares_read_nothing_without_the_annotations():
    red = _reduction(0.0)
    red.host = [h for h in red.host if not h[0].startswith("broker.")]
    run = _run(reduction=red, window_s=0.04)
    assert _read("device_idle_share.flush_wait", run) is None
    assert _read("device_idle_share.broker_host", run) is None
    assert _read("device_idle_share.flush_wait", _run()) is None


def test_overlap_of_interval_lists():
    assert annotations.overlap_us([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert annotations.overlap_us([], [(0, 1)]) == 0
