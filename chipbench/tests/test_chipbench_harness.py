"""CPU tests of the benchmark harness: discovery by name, the contract's
character rules, the trace reduction, the peaks table and work functions,
the references and generators, and the refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import peaks, reference, spec, tracereduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_names_and_units_use_the_allowed_characters(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in bench[group]]
        assert len(seen) == len(set(seen)), group


def test_every_cell_reports_what_its_per_layer_metrics_move(bench):
    for cell in bench["workloads"]:
        e2e, layer = spec.cell_metrics(bench, cell["name"])
        reported = {m["name"] for m in e2e}
        assert "setup_s" in reported and len(reported) >= 2, cell["name"]
        assert layer, cell["name"]
        for m in layer:
            assert m["moves"] in reported, (cell["name"], m["name"])


def test_every_part_is_found_by_name(bench):
    for cell in bench["workloads"]:
        wl = spec.load_workload(cell["name"])
        assert wl["config"] == cell["config"]
        assert spec.load_config(wl["config"])["name"] == cell["config"]
    for m in bench["per_layer"]:
        assert callable(spec.load_reader(m["name"]))
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()


def test_new_files_are_picked_up_without_edits(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "workloads").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "cfg_x.json").write_text(json.dumps({"name": "cfg_x"}))
    (tmp_path / "workloads" / "cell.x.json").write_text(json.dumps({"config": "cfg_x"}))
    (tmp_path / "metrics" / "metric.x.py").write_text("def read(run):\n    return 42.0\n")
    wl = spec.load_workload("cell.x", tmp_path)
    assert spec.load_config(wl["config"], tmp_path) == {"name": "cfg_x"}
    assert spec.load_reader("metric.x", tmp_path)(None) == 42.0
    bench = {
        "end_to_end": [{"name": "setup_s"}, {"name": "a", "workloads": ["other"]}],
        "per_layer": [{"name": "metric.x", "moves": "setup_s"},
                      {"name": "y", "moves": "a"},
                      {"name": "z", "moves": "a", "workloads": ["cell.x"]}],
    }
    e2e, layer = spec.cell_metrics(bench, "cell.x")
    assert [m["name"] for m in e2e] == ["setup_s"]
    assert [m["name"] for m in layer] == ["metric.x", "z"]


def _trace(events):
    meta = [
        {"ph": "M", "pid": 1, "name": "process_name", "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": 7, "name": "process_name", "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": 7, "tid": 2, "name": "thread_name", "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": 7, "tid": 1, "name": "thread_name", "args": {"name": "XLA Modules"}},
        {"ph": "M", "pid": 7, "tid": 3, "name": "thread_name", "args": {"name": "Steps"}},
    ]
    return meta + events


def test_trace_reduction_reads_the_whole_device_timeline():
    # first profiler session: the device events lie before the host
    # annotation (which starts at 1000 us); they still count, once each
    events = _trace([
        {"ph": "X", "pid": 1, "tid": 9, "name": "window", "ts": 1000.0, "dur": 500.0},
        {"ph": "X", "pid": 1, "tid": 9, "name": "dispatch", "ts": 300.0, "dur": 60.0},
        {"ph": "X", "pid": 7, "tid": 1, "name": "jit_scan", "ts": 100.0, "dur": 100.0},
        {"ph": "X", "pid": 7, "tid": 2, "name": "fusion.1", "ts": 110.0, "dur": 40.0},
        {"ph": "X", "pid": 7, "tid": 2, "name": "fusion.2", "ts": 160.0, "dur": 30.0},
        {"ph": "X", "pid": 7, "tid": 1, "name": "jit_scan", "ts": 400.0, "dur": 50.0},
        {"ph": "X", "pid": 7, "tid": 2, "name": "fusion.1", "ts": 410.0, "dur": 40.0},
        # a step marker spans the idle gap; it is not work
        {"ph": "X", "pid": 7, "tid": 3, "name": "step 0", "ts": 100.0, "dur": 350.0},
    ])
    red = tracereduce.reduce_events(events)
    (dev,) = red.devices
    assert dev.busy_us == pytest.approx(150.0)
    assert red.mean_busy_s() == pytest.approx(150e-6)
    assert red.top_ops(1) == [["fusion.1", pytest.approx(80e-6)]]
    # the one gap (200..400 us) overlaps the host's dispatch event most
    assert red.idle_gaps() == [["dispatch", pytest.approx(200e-6)]]
    assert dev.lines == {"XLA Modules": 2, "XLA Ops": 3, "Steps": 1}


def test_trace_reduction_without_a_device_reads_nothing():
    red = tracereduce.reduce_events(_trace([
        {"ph": "X", "pid": 1, "tid": 9, "name": "host", "ts": 0.0, "dur": 10.0},
    ])[:1] + [{"ph": "X", "pid": 1, "tid": 9, "name": "host", "ts": 0.0, "dur": 10.0}])
    assert red.devices == [] and red.busiest() is None and red.top_ops() == []


def test_peaks_table_refuses_an_unknown_device():
    assert peaks.for_device("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_device("TPU v99")


def test_work_functions_match_hand_counts():
    m = spec.load_config("mamba2_130m_train")["model"]
    # per layer: in-proj 768*(2*1536+2*128+24) + out 1536*768 + conv 4*1792
    # + conv bias 1792 + A_log/D/dt_bias 3*24 + gate-norm 1536 + norm 768
    layer = 2_574_336 + 1_179_648 + 7_168 + 1_792 + 72 + 1_536 + 768
    assert peaks.mamba2_param_count(m) == 24 * layer + 50280 * 768 + 768 == 128_983_488
    matmul = 24 * (2_574_336 + 1_179_648 + 7_168) + 50280 * 768
    ssd = 24 * (2 * 256 * 128 + 2 * 256 * 24 * 64 + 4 * 24 * 64 * 128)
    assert peaks.mamba2_flops_per_token(m) == 3 * (2 * matmul + ssd)
    assert peaks.collective_least_bytes(8, 64 << 20) == 2 * 8 * 64 * 2**20


def test_reference_semantics_and_the_control_break_them():
    rng = np.random.default_rng(0)
    x = rng.integers(-(2**31), 2**31, size=(8, 5), dtype=np.int32)
    scan = reference.collective("SCAN", "sum", x)
    want = np.cumsum(x.astype(np.int64), axis=0)
    assert np.array_equal(scan, ((want + 2**31) % 2**32 - 2**31).astype(np.int32))
    ex = reference.collective("EXSCAN", "sum", x)
    assert not ex[0].any() and np.array_equal(ex[1:], scan[:-1])
    assert np.array_equal(reference.collective("ALLREDUCE", "sum", x)[3], scan[-1])
    f = rng.standard_normal((8, 5)).astype(np.float32)
    assert np.array_equal(reference.collective("SCAN", "max", f), np.maximum.accumulate(f))
    assert not np.array_equal(reference.control("SCAN", "sum", x), scan)
    assert not np.array_equal(reference.control("SCAN", "max", f),
                              reference.collective("SCAN", "max", f))


def test_open_loop_offers_the_same_work_for_every_seed():
    wl = spec.load_workload("broker.zipf_small")
    d1, t1 = traffic.open_loop_schedule(wl, 2**33 + 1, 2.0, 500)
    d2, t2 = traffic.open_loop_schedule(wl, 7, 2.0, 500)
    assert len(d1) == len(d2) == 1000 and np.all(np.diff(d1) >= 0)
    assert np.array_equal(np.bincount(t1, minlength=64), np.bincount(t2, minlength=64))
    assert not np.array_equal(t1, t2)
    d3, t3 = traffic.open_loop_schedule(wl, 7, 2.0, 500)
    assert np.array_equal(d2, d3) and np.array_equal(t2, t3)
    counts = traffic.zipf_counts(1000, 64, 0.99)
    assert counts.sum() == 1000 and counts[0] > counts[1] > counts[-1]
    assert traffic.tenant_shapes(wl) == traffic.tenant_shapes(dict(wl))


def test_packed_batches_are_seeded_rows_of_documents():
    a = traffic.take(traffic.packed_batches(256, 64, 4, 2**33 + 5, mean_doc_len=32), 3)
    b = traffic.take(traffic.packed_batches(256, 64, 4, 2**33 + 5, mean_doc_len=32), 3)
    for x, y in zip(a, b):
        assert np.array_equal(x["tokens"], y["tokens"])
        assert x["tokens"].shape == (4, 64) and x["tokens"].dtype == np.int32
        assert np.array_equal(x["tokens"][:, 1:], x["labels"][:, :-1])
    assert not np.array_equal(a[0]["tokens"], a[1]["tokens"])


def test_a_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         "broker.zipf_small", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(ROOT),
    )
    assert proc.returncode != 0
    assert "metrics" not in proc.stdout and "no TPU" in proc.stderr
