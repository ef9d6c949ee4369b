"""Observability tests: span-tree invariants from a traced dispatch,
traced-vs-jitted bitwise identity, Perfetto export round-trip (including
the empty span list) and host+device merge alignment plus its degrade
paths (missing/truncated/malformed device traces must record a reason,
never raise), the Prometheus exposition format and label escaping,
profiler fallback accounting, latency-histogram edge cases (including a
threaded stress test), broker request spans, and the obs_check CI
module. The health stack (flight recorder, SLOs, link attribution) is
covered by tests/test_health.py."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.offload import OffloadEngine, build_plan, lower_sim, optimize_plan
from repro.service import DescriptorBroker, LatencyHistogram
from repro.service.telemetry import LATENCY_BUCKETS_US

AXES = (2, 4)
P = 8
N = 16


@pytest.fixture(autouse=True)
def _clean_obs():
    obs_tracing.set_tracer(None)
    obs_metrics.reset_registry()
    yield
    obs_tracing.set_tracer(None)
    obs_metrics.reset_registry()


def _x(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-5, 6, size=(P, N)).astype(np.float32))


def _traced_scan_spans():
    eng = OffloadEngine()
    desc = eng.make_descriptor(
        "scan", axes=AXES, payload_bytes=N * 4, op="sum", optimize=True
    )
    x = _x()
    with obs_tracing.tracing() as tracer:
        out = eng.offload(desc, x)
    return eng, desc, x, np.asarray(out), tracer.spans()


# ------------------------------------------------------------ span tree


def test_traced_dispatch_span_tree_invariants():
    """engine.offload -> phase -> round, parents contain children, round
    spans per comm phase match the phase's own round count."""
    _, _, _, _, spans = _traced_scan_spans()
    by_id = {s.span_id: s for s in spans}
    roots = [s for s in spans if s.name == "engine.offload"]
    assert len(roots) == 1
    phases = [s for s in spans if s.cat == "phase"]
    rounds = [s for s in spans if s.cat == "round"]
    assert phases and rounds
    # every phase hangs off the engine span; every round off a phase
    for ph in phases:
        assert by_id[ph.parent_id].cat == "engine"
    for r in rounds:
        assert by_id[r.parent_id].cat == "phase"
    # containment: child window inside parent window
    for s in spans:
        parent = by_id.get(s.parent_id)
        if parent is not None:
            assert parent.start_us <= s.start_us
            assert s.end_us <= parent.end_us + 1e-3
    # comm phases declare their round count; the round spans must match
    comm = [ph for ph in phases if ph.args.get("rounds", 0) > 0]
    assert comm
    for ph in comm:
        children = [r for r in rounds if r.parent_id == ph.span_id]
        assert len(children) == ph.args["rounds"]
        # rounds are ordered and indexed from 0 within their phase
        assert [r.args["round"] for r in children] == list(
            range(len(children))
        )
        assert all(
            a.start_us <= b.start_us for a, b in zip(children, children[1:])
        )


def test_traced_result_bitwise_equals_jitted():
    """The traced eager interpreter must not change a single bit, and the
    jitted schedule must stay cached independently of the traced one."""
    eng, desc, x, traced_out, _ = _traced_scan_spans()
    baseline = np.asarray(eng.offload(desc, x))  # noop tracer -> jitted
    np.testing.assert_array_equal(traced_out, baseline)
    # both the jitted and the traced variant live in the schedule cache;
    # re-dispatching either is a cache hit
    before = eng.telemetry.snapshot()["misses"]
    np.testing.assert_array_equal(np.asarray(eng.offload(desc, x)), baseline)
    with obs_tracing.tracing():
        np.testing.assert_array_equal(
            np.asarray(eng.offload(desc, x)), baseline
        )
    assert eng.telemetry.snapshot()["misses"] == before


def test_noop_tracer_is_default_and_collects_nothing():
    tracer = obs_tracing.get_tracer()
    assert isinstance(tracer, obs_tracing.NoopTracer)
    assert not tracer.enabled
    with tracer.span("anything", "engine") as sp:
        sp.set(ignored=1)
    assert tracer.spans() == ()
    assert tracer.current_span_id() is None


def test_telemetry_snapshot_keys_unchanged_by_tracing():
    """The obs layer adds keys; it must not rename or drop existing ones."""
    eng, desc, x, _, _ = _traced_scan_spans()
    snap = eng.telemetry.snapshot()
    for key in (
        "hits", "misses", "hit_rate", "dispatches", "compiles", "errors",
        "cache_size", "cache_clears", "calls_by_coll", "mean_latency_us",
        "last_latency_us", "latency_by_coll_us",
        "device_latency_by_coll_us", "latency_source_by_coll",
    ):
        assert key in snap
    assert snap["profiler_fallbacks"] == 0
    assert snap["profiler_fallback_reasons"] == {}


def test_plan_level_tracing_via_lower_sim():
    """lower_sim(traced=True) emits spans without any engine involved."""
    plan = optimize_plan(
        build_plan("scan", AXES, "sum", N * 4, order=(0, 1))
    )
    fn = lower_sim(plan, traced=True)
    x = _x(1)
    with obs_tracing.tracing() as tracer:
        out = fn(x)
    want = np.asarray(jnp.asarray(lower_sim(plan)(x)))
    np.testing.assert_array_equal(np.asarray(out), want)
    cats = {s.cat for s in tracer.spans()}
    assert "phase" in cats and "round" in cats


def test_add_span_cross_thread_parent_links():
    """add_span records retroactive spans with explicit parents — the
    broker's queue-wait pattern — and keeps ordering by start time."""
    tracer = obs_tracing.Tracer()
    t0 = obs_tracing.now_us()
    root = tracer.add_span("service.submit", "service", t0, t0 + 5.0)
    child = tracer.add_span(
        "broker.queue_wait", "broker", t0 + 5.0, t0 + 9.0, parent_id=root
    )
    spans = tracer.spans()
    assert [s.span_id for s in spans] == [root, child]
    assert spans[1].parent_id == root
    assert spans[1].dur_us == pytest.approx(4.0)


# ------------------------------------------------------------ export


def test_chrome_round_trip_is_lossless():
    _, _, _, _, spans = _traced_scan_spans()
    trace = obs_export.spans_to_chrome(spans)
    # Perfetto/chrome essentials: metadata + complete events on the host pid
    assert any(e["ph"] == "M" for e in trace["traceEvents"])
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(spans)
    assert all(e["pid"] == obs_export.HOST_PID for e in xs)
    back = obs_export.chrome_to_spans(trace)
    assert len(back) == len(spans)
    for a, b in zip(sorted(spans, key=lambda s: s.span_id),
                    sorted(back, key=lambda s: s.span_id)):
        assert (a.name, a.cat, a.span_id, a.parent_id) == (
            b.name, b.cat, b.span_id, b.parent_id
        )
        assert a.start_us == pytest.approx(b.start_us)
        assert a.dur_us == pytest.approx(b.dur_us)


def test_merge_device_trace_aligns_on_anchor():
    """A synthetic device trace sharing one event name with the host trace
    gets its clock shifted so the anchors coincide."""
    tracer = obs_tracing.Tracer()
    t0 = obs_tracing.now_us()
    tracer.add_span("repro_offload:scan:p8", "profile", t0, t0 + 100.0)
    host = obs_export.spans_to_chrome(tracer.spans())
    device = {
        "traceEvents": [
            {"ph": "X", "name": "repro_offload:scan:p8", "ts": 5000.0,
             "dur": 100.0, "pid": 9, "tid": 1},
            {"ph": "X", "name": "TfrtCpuExecutable::Execute", "ts": 5010.0,
             "dur": 42.0, "pid": 9, "tid": 1},
        ]
    }
    merged = obs_export.merge_device_trace(host, device)
    assert merged["deviceClockAligned"] is True
    assert merged["deviceEventsMerged"] >= 1
    dev = [
        e for e in merged["traceEvents"]
        if e.get("pid") == obs_export.DEVICE_PID and e.get("ph") == "X"
        and e["name"] != "repro_offload:scan:p8"
    ]
    assert dev
    # anchor was at ts=5000 on the device clock, t0 on the host clock:
    # the executable event 10us after the anchor lands 10us after t0
    assert dev[0]["ts"] == pytest.approx(t0 + 10.0)


def test_merge_without_common_event_keeps_device_clock():
    host = obs_export.spans_to_chrome(())
    device = {"traceEvents": [
        {"ph": "X", "name": "XlaModule:foo", "ts": 1.0, "dur": 2.0,
         "pid": 3, "tid": 4},
    ]}
    merged = obs_export.merge_device_trace(host, device)
    assert merged["deviceClockAligned"] is False
    assert merged["deviceEventsMerged"] == 1


def test_chrome_round_trip_empty_span_list():
    """Zero spans is a valid trace: metadata only out, zero spans back."""
    trace = obs_export.spans_to_chrome(())
    assert all(e["ph"] == "M" for e in trace["traceEvents"])
    assert obs_export.chrome_to_spans(trace) == []


def test_merge_missing_device_trace_degrades(tmp_path):
    """A nonexistent device-trace path must not raise: the merged result
    is the host trace with the failure reason recorded, and the degrade
    lands in the flight recorder as a profiler_fallback event."""
    from repro.obs import events as obs_events

    rec = obs_events.FlightRecorder()
    prev = obs_events.set_recorder(rec)
    try:
        host = obs_export.spans_to_chrome(())
        merged = obs_export.merge_device_trace(
            host, tmp_path / "never_written.json.gz"
        )
    finally:
        obs_events.set_recorder(prev)
    assert merged["deviceEventsMerged"] == 0
    assert merged["deviceClockAligned"] is False
    assert "unreadable" in merged["deviceMergeError"]
    assert len(host["traceEvents"]) == len(merged["traceEvents"])
    falls = rec.events(kind="profiler_fallback")
    assert falls and falls[0]["reason"] == "merge_unreadable_trace"


def test_merge_unparseable_device_trace_degrades(tmp_path):
    """Truncated JSON (the profiler died mid-write) degrades with a
    recorded reason instead of taking down the host-trace export."""
    bad = tmp_path / "truncated.json"
    bad.write_text('{"traceEvents": [{"ph": "X", "name": "XlaModule')
    host = obs_export.spans_to_chrome(())
    merged = obs_export.merge_device_trace(host, bad)
    assert merged["deviceEventsMerged"] == 0
    assert "unreadable" in merged["deviceMergeError"]


def test_merge_non_object_device_trace_degrades(tmp_path):
    """Valid JSON of the wrong shape (a list) is malformed, not a crash."""
    bad = tmp_path / "list.json"
    bad.write_text('[{"ph": "X"}]')
    merged = obs_export.merge_device_trace(
        obs_export.spans_to_chrome(()), bad
    )
    assert merged["deviceEventsMerged"] == 0
    assert "malformed" in merged["deviceMergeError"]
    assert "list" in merged["deviceMergeError"]


def test_write_trace_and_load(tmp_path):
    _, _, _, _, spans = _traced_scan_spans()
    out = tmp_path / "trace.json"
    obs_export.write_trace(out, obs_export.spans_to_chrome(spans))
    loaded = obs_export.load_chrome_trace(out)
    assert len(loaded["traceEvents"]) >= len(spans)


# ------------------------------------------------------------ metrics


def test_prometheus_exposition_format():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("repro_test_total", "a counter", labelnames=("coll",))
    c.inc(coll="scan")
    c.inc(2, coll="scan")
    g = reg.gauge("repro_test_depth", "a gauge")
    g.set(3.5)
    h = reg.histogram(
        "repro_test_us", "a histogram", buckets=(1.0, 10.0)
    )
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.render()
    assert "# HELP repro_test_total a counter" in text
    assert "# TYPE repro_test_total counter" in text
    assert 'repro_test_total{coll="scan"} 3' in text
    assert "repro_test_depth 3.5" in text
    # cumulative buckets + the +Inf catch-all, sum and count
    assert 'repro_test_us_bucket{le="1"} 1' in text
    assert 'repro_test_us_bucket{le="10"} 2' in text
    assert 'repro_test_us_bucket{le="+Inf"} 3' in text
    assert "repro_test_us_sum 105.5" in text
    assert "repro_test_us_count 3" in text


def test_prometheus_label_escaping():
    """Backslash, quote, and newline in a label value must arrive escaped
    per the exposition format — a tenant named "a\\b" or containing a
    newline must not corrupt the scrape."""
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("repro_esc_total", "escapes", labelnames=("tenant",))
    c.inc(tenant='quo"te')
    c.inc(tenant="back\\slash")
    c.inc(tenant="new\nline")
    text = reg.render()
    assert 'repro_esc_total{tenant="quo\\"te"} 1' in text
    assert 'repro_esc_total{tenant="back\\\\slash"} 1' in text
    assert 'repro_esc_total{tenant="new\\nline"} 1' in text
    assert "\nline" not in text.replace("\\n", "")  # no raw newline leaked


def test_registry_get_or_create_conflicts():
    reg = obs_metrics.MetricsRegistry()
    c = reg.counter("repro_x_total", "x")
    assert reg.counter("repro_x_total", "x") is c
    with pytest.raises(ValueError):
        reg.gauge("repro_x_total", "x")
    with pytest.raises(ValueError):
        reg.counter("repro_x_total", "x", labelnames=("coll",))
    with pytest.raises(ValueError):
        c.inc(-1.0)


def test_round_bucket_labels():
    assert obs_metrics.round_bucket(0) == "0"
    assert obs_metrics.round_bucket(3) == "3"
    assert obs_metrics.round_bucket(4) == "4-7"
    assert obs_metrics.round_bucket(9) == "8-15"
    assert obs_metrics.round_bucket(100) == "64-127"


def test_dispatch_publishes_engine_metrics():
    eng = OffloadEngine()
    desc = eng.make_descriptor(
        "scan", axes=AXES, payload_bytes=N * 4, op="sum", optimize=True
    )
    eng.offload(desc, _x())
    text = obs_metrics.render_prometheus()
    assert 'repro_engine_dispatches_total{coll="scan"} 1' in text
    assert "repro_engine_dispatch_latency_us_bucket" in text
    assert 'repro_engine_cache_events_total{event="miss"} 1' in text
    with obs_tracing.tracing():
        eng.offload(desc, _x())
    text = obs_metrics.render_prometheus()
    # the traced dispatch observed per-round and per-phase histograms
    assert "repro_round_latency_us_bucket" in text
    assert 'phase_kind="SCAN"' in text


# ------------------------------------------------------------ profiling


def test_profiler_fallback_reason_is_counted(monkeypatch):
    """A profiler that cannot start degrades to wall source AND surfaces
    the reason in telemetry + metrics instead of failing silently."""
    import jax

    eng = OffloadEngine()
    desc = eng.make_descriptor(
        "scan", axes=AXES, payload_bytes=N * 4, op="sum", optimize=True
    )

    def boom(*a, **k):
        raise RuntimeError("another profiler session is active")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    t = eng.profile_offload(desc, _x())
    assert t.source == "wall"
    assert t.fallback_reason == "trace_start_failed"
    snap = eng.telemetry.snapshot()
    assert snap["profiler_fallbacks"] == 1
    assert snap["profiler_fallback_reasons"] == {"trace_start_failed": 1}
    assert (
        'repro_engine_profiler_fallbacks_total'
        '{coll="scan",reason="trace_start_failed"} 1'
    ) in obs_metrics.render_prometheus()


@pytest.mark.parametrize(
    "device_plane,offset",
    [(True, 0.0), (True, 5000.0), (False, 0.0)],
    ids=["tpu", "tpu_clock_offset", "cpu"],
)
def test_parse_device_us_reads_the_device_timeline(
    tmp_path, device_plane, offset
):
    """An accelerator trace is timed from its /device: process alone (host
    events that happen to match the executable regex do not count), all of
    it: the session holds one dispatch, and a device clock that is offset
    from the host's must not push it out of the annotation's window. A
    trace without one falls back to the host's executable events inside
    the window."""
    import gzip
    import json

    from repro.offload import parse_device_us

    events = [
        {"ph": "M", "name": "process_name", "pid": 1,
         "args": {"name": "/host:CPU"}},
        {"ph": "X", "name": "repro_offload:scan:p8", "pid": 1,
         "ts": 100.0, "dur": 100.0},
        {"ph": "X", "name": "TfrtCpuExecutable::Execute", "pid": 1,
         "ts": 110.0, "dur": 50.0},
    ]
    if device_plane:
        events += [
            {"ph": "M", "name": "process_name", "pid": 7,
             "args": {"name": "/device:TPU:0"}},
            {"ph": "X", "name": "jit_scan", "pid": 7, "ts": 120.0 + offset,
             "dur": 30.0},
            {"ph": "X", "name": "fusion.1", "pid": 7, "ts": 125.0 + offset,
             "dur": 10.0},
            {"ph": "X", "name": "jit_other", "pid": 7, "ts": 190.0 + offset,
             "dur": 40.0},
        ]
    path = tmp_path / "t.trace.json.gz"
    path.write_bytes(gzip.compress(json.dumps(
        {"traceEvents": events}
    ).encode()))
    got = parse_device_us(str(path), "repro_offload:scan:p8")
    # device: [120,150) and [190,230), unclipped; host: [110,160)
    assert got == ((70.0, 3) if device_plane else (50.0, 1))


# ------------------------------------------------------ latency histogram


def test_latency_histogram_edge_cases():
    h = LatencyHistogram()
    # empty: every quantile is 0, not a bucket edge
    assert h.percentile_us(0.0) == 0.0
    assert h.percentile_us(0.5) == 0.0
    assert h.percentile_us(1.0) == 0.0
    with pytest.raises(ValueError):
        h.percentile_us(1.5)
    with pytest.raises(ValueError):
        h.percentile_us(-0.1)
    # single sample: all quantiles collapse to it (not to the 50us edge)
    h.record(10e-6)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.percentile_us(q) == pytest.approx(10.0)
    assert h.min_us == pytest.approx(10.0)
    assert h.max_us == pytest.approx(10.0)
    # open-bucket sample reports the observed max, not infinity
    h2 = LatencyHistogram()
    big = (LATENCY_BUCKETS_US[-1] * 3) * 1e-6
    h2.record(big)
    assert h2.percentile_us(0.99) == pytest.approx(big * 1e6)
    # percentiles never leave [min, max]
    h3 = LatencyHistogram()
    h3.record(60e-6)
    h3.record(70e-6)  # both in the (50, 100] bucket
    assert h3.percentile_us(0.5) == pytest.approx(70.0)
    assert h3.percentile_us(0.0) == pytest.approx(60.0)


def test_latency_histogram_threaded_stress():
    """Concurrent recorders + snapshot readers: totals conserve and no
    reader ever observes torn state."""
    h = LatencyHistogram()
    n_threads, per_thread = 8, 500
    errors = []

    def writer(seed):
        rng = np.random.default_rng(seed)
        for _ in range(per_thread):
            h.record(float(rng.uniform(1e-6, 2e-1)))

    def reader():
        for _ in range(200):
            snap = h.snapshot()
            if snap["count"]:
                lo, hi = snap["min_us"], snap["max_us"]
                mean, p50 = snap["mean_us"], snap["p50_us"]
                if not (lo <= mean <= hi and lo <= p50 <= hi):
                    errors.append(snap)

    threads = [
        threading.Thread(target=writer, args=(i,)) for i in range(n_threads)
    ] + [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert h.count == n_threads * per_thread
    assert sum(h.counts) == h.count
    assert h.min_us <= h.percentile_us(0.5) <= h.max_us


# ------------------------------------------------------------ broker


def test_broker_request_spans_link_submit_to_dispatch():
    """service.submit -> broker.queue_wait -> broker.dispatch_group ->
    engine.offload, linked by explicit parent ids across threads."""
    with obs_tracing.tracing() as tracer:
        broker = DescriptorBroker(OffloadEngine())
        desc = broker.make_descriptor(
            "SCAN", p=P, payload_bytes=N * 4, op="sum"
        )
        ticket = broker.client("t0").submit(desc.encode(), _x())
        assert broker.drain() == 1
        ticket.result(5)
    spans = tracer.spans()
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, s)
    submit = by_name.get("service.submit")
    wait = by_name.get("broker.queue_wait")
    group = by_name.get("broker.dispatch_group")
    assert submit is not None and wait is not None and group is not None
    assert wait.parent_id == submit.span_id
    assert submit.args["tenant"] == "t0"
    assert submit.args["coll"] == "scan"
    # the engine span belongs to the dispatch-group window
    engine = [s for s in spans if s.name == "engine.offload"]
    assert engine and engine[0].parent_id == group.span_id


def _children(spans, parent):
    return [s for s in spans if s.parent_id == parent.span_id]


def test_broker_drain_nests_the_group_work_under_dispatch_group():
    """A fused group: stack, the engine's launch and device wait, unstack
    and fulfil all sit inside its dispatch_group, whose request ids are the
    queue_wait spans' ones."""
    with obs_tracing.tracing() as tracer:
        broker = DescriptorBroker(OffloadEngine())
        desc = broker.make_descriptor(
            "SCAN", p=P, payload_bytes=N * 4, op="sum"
        )
        tickets = [
            broker.client(t).submit(desc.encode(), _x(i))
            for i, t in enumerate(("t0", "t1", "t2"))
        ]
        assert broker.drain() == 3
        for t in tickets:
            t.result(5)
    spans = tracer.spans()
    (group,) = [s for s in spans if s.name == "broker.dispatch_group"]
    kids = {s.name: s for s in _children(spans, group)}
    assert set(kids) == {
        "broker.stack", "engine.offload", "broker.unstack", "broker.fulfil"
    }
    order = ["broker.stack", "engine.offload", "broker.unstack",
             "broker.fulfil"]
    starts = [kids[n].start_us for n in order]
    assert starts == sorted(starts)
    engine_kids = {s.name for s in _children(spans, kids["engine.offload"])}
    assert {"engine.launch", "engine.device_wait"} <= engine_kids
    waits = [s for s in spans if s.name == "broker.queue_wait"]
    assert sorted(group.args["requests"]) == sorted(
        w.args["request"] for w in waits
    ) == ["t0#0", "t1#0", "t2#0"]
    assert all(s.tid == group.tid for s in kids.values())


def test_broker_thread_waits_are_spans_on_the_dispatch_thread():
    """Under the running flush thread: idle (nothing queued) and
    flush_wait (the deadline, with the queue depth) are spans of the
    dispatch thread, beside its dispatch_group."""
    with obs_tracing.tracing() as tracer:
        broker = DescriptorBroker(OffloadEngine(), flush_interval_s=0.02)
        desc = broker.make_descriptor(
            "SCAN", p=P, payload_bytes=N * 4, op="sum"
        )
        client = broker.client("t0")
        broker.start()
        try:
            time.sleep(0.2)  # the thread waits, idle, for a request
            tickets = [client.submit(desc.encode(), _x(i)) for i in range(2)]
            for t in tickets:
                t.result(30)
        finally:
            broker.stop()
    spans = tracer.spans()
    groups = [s for s in spans if s.name == "broker.dispatch_group"]
    assert groups
    thread = {g.tid for g in groups}
    assert len(thread) == 1
    flush = [s for s in spans if s.name == "broker.flush_wait"]
    idle = [s for s in spans if s.name == "broker.idle"]
    assert flush and idle
    assert {s.tid for s in flush + idle} == thread
    assert all(s.args["queued"] >= 1 for s in flush)
    assert all(s.parent_id is None for s in flush + idle)
    served = [r for g in groups for r in g.args["requests"]]
    waits = [s.args["request"] for s in spans if s.name == "broker.queue_wait"]
    assert sorted(served) == sorted(waits) == ["t0#0", "t0#1"]


def test_tracer_spans_are_profiler_host_events(tmp_path):
    """A collecting tracer's context span is also a host event, by name, in
    a jax.profiler trace; a retroactive add_span is not."""
    import glob
    import gzip
    import json

    import jax

    tracer = obs_tracing.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracer.span("test.annotated", "host"):
            jnp.ones(4).block_until_ready()
        t = obs_tracing.now_us()
        tracer.add_span("test.retroactive", "host", t - 10.0, t)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.trace.json.gz"),
                        recursive=True)
    with gzip.open(path, "rb") as f:
        events = json.loads(f.read())["traceEvents"]
    names = {e.get("name") for e in events if e.get("ph") == "X"}
    assert "test.annotated" in names
    assert "test.retroactive" not in names
    assert [s.name for s in tracer.spans()] == [
        "test.annotated", "test.retroactive"
    ]


# ------------------------------------------------------------ round count


def _eager_rounds(coll, p, algo, x):
    """Round spans and TracingBackend.rounds of the schedule run eagerly."""
    from repro.core import algorithms as alg
    from repro.core.operators import get_operator
    from repro.core.reduce_ops import (
        allreduce_schedule, barrier_schedule, reduce_schedule,
    )
    from repro.core.scan_collective import sim_scan

    op = get_operator("sum")
    run = {
        "scan": lambda b: sim_scan(x, op, p, algorithm=algo, backend=b),
        "exscan": lambda b: sim_scan(
            x, op, p, algorithm=algo, inclusive=False, backend=b
        ),
        "reduce": lambda b: reduce_schedule(b, x, op, algorithm=algo),
        "allreduce": lambda b: allreduce_schedule(b, x, op, algorithm=algo),
        "barrier": lambda b: barrier_schedule(b, algorithm=algo),
    }[coll]
    with obs_tracing.tracing() as tracer:
        backend = obs_tracing.TracingBackend(alg.SimBackend(p), tracer)
        run(backend)
    spans = [s for s in tracer.spans() if s.cat == "round"]
    return backend.rounds, len(spans)


@pytest.mark.parametrize("p", [2, 8])
@pytest.mark.parametrize(
    "coll", ["scan", "exscan", "reduce", "allreduce", "barrier"]
)
def test_compiled_rounds_equal_the_eager_traced_count(coll, p):
    from repro.core.packet import WireDType

    eng = OffloadEngine()
    desc = eng.make_descriptor(
        coll, p=p, payload_bytes=N * 4, op="sum", data_type=WireDType.INT32
    )
    x = jnp.arange(p * N, dtype=jnp.int32).reshape(p, N)
    eng.offload(desc, x)
    (sched,) = eng._cache.values()
    counted, round_spans = _eager_rounds(coll, p, desc.algo_type, x)
    assert sched.rounds == counted == round_spans > 0
    if coll == "scan" and p == 8:
        assert sched.rounds == 3


def test_planned_rounds_are_the_sum_over_phases():
    """A planned schedule's round count is the sum of its phases' rounds,
    as the traced interpreter's phase spans report them."""
    eng, desc, x, _, spans = _traced_scan_spans()
    (offload,) = [s for s in spans if s.name == "engine.offload"]
    phase_rounds = sum(s.args.get("rounds", 0) for s in spans
                       if s.cat == "phase")
    assert offload.args["rounds"] == phase_rounds > 0
    eng.offload(desc, x)  # the jitted schedule: counted when it compiles
    assert {s.rounds for s in eng._cache.values()} == {phase_rounds}


def test_rounds_dispatched_sums_the_offload_spans_rounds():
    """EngineTelemetry.rounds_dispatched over a window equals the sum of
    the rounds args on that window's engine.offload spans, which also
    carry the schedule's algo and the bytes per rank."""
    from repro.core.packet import WireDType

    eng = OffloadEngine()
    descs = [
        eng.make_descriptor(c, p=P, payload_bytes=N * 4, op="sum",
                            data_type=WireDType.INT32)
        for c in ("scan", "exscan", "allreduce")
    ]
    x = jnp.ones((P, N), jnp.int32)
    eng.offload(descs[0], x)  # before the window
    before = eng.telemetry.snapshot()["rounds_dispatched"]
    with obs_tracing.tracing() as tracer:
        for d in descs + descs[:1]:
            eng.offload(d, x)
    after = eng.telemetry.snapshot()["rounds_dispatched"]
    offloads = [s for s in tracer.spans() if s.name == "engine.offload"]
    assert len(offloads) == 4
    assert after - before == sum(s.args["rounds"] for s in offloads) > 0
    assert all(s.args["bytes_per_rank"] == N * 4 for s in offloads)
    assert offloads[0].args["algo"] == eng._cache[
        eng._cache_key(descs[0], None)
    ].algo


# ------------------------------------------------------------ CI module


def test_obs_check_module(subprocess_runner):
    out = subprocess_runner("repro.testing.obs_check", "2", "2")
    assert "obs_check_summary,bitwise_equal,1," in out
