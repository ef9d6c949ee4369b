"""CPU tests of the benchmark harness: discovery by name, the contract's
character rules, the trace reduction, the peaks table and work functions,
the references and generators, and the refusal to run without a TPU."""

import json
import os
import re
import subprocess
import sys
import time
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


#: a system of its own: a window of ``calls`` calls of 1 ms each, counted,
#: judged by the limit its yardstick gives
TOY_SYSTEM = """
import time

from chipbench import spec
from chipbench.systems import Check, Window


class Cell:
    def __init__(self, config, workload, *, seed, seconds, devices, scratch,
                 tracing=False):
        self.ref = spec.load_yardstick(config["reference"])
        self.n = int(workload["calls"])
        self.calls = 0

    def setup(self):
        pass

    def counters(self):
        return {"toy": {"calls": self.calls}}

    def window(self):
        t0 = time.perf_counter()
        self.calls += self.n
        return Window(start=t0, end=t0 + 1.0, latencies_s=[1e-3] * self.n,
                      attempted=self.n)

    def spans(self):
        return None

    def release(self):
        pass

    def check(self, control=False):
        return [Check("wrong_answers", int(control), self.ref.LIMITS["wrong_answers"])]
"""

TOY_REF = """
LIMITS = {"wrong_answers": 0}


def flops_per_token(model):
    return 6.0 * model["n"]
"""


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A system ``toy``, a yardstick ``toy_ref``, a configuration, a cell and
    two readers, as new files in a directory of their own that the package
    ``chipbench`` searches besides its own."""
    import importlib

    import chipbench
    from chipbench import systems

    for d in ("configs", "workloads", "metrics", "systems"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "cfg_x.json").write_text(json.dumps(
        {"name": "cfg_x", "system": "toy", "reference": "toy_ref", "model": {"n": 2}}))
    (tmp_path / "workloads" / "cell.x.json").write_text(
        json.dumps({"config": "cfg_x", "calls": 5}))
    (tmp_path / "metrics" / "metric.x.py").write_text("def read(run):\n    return 42.0\n")
    (tmp_path / "metrics" / "toy_calls.py").write_text(
        "def read(run):\n    return run.counter_delta('toy', 'calls')\n")
    (tmp_path / "metrics" / "toy_flops.py").write_text(
        "from chipbench import spec\n\n\ndef read(run):\n"
        "    ref = spec.load_yardstick(run.config['reference'])\n"
        "    return ref.flops_per_token(run.config['model'])\n")
    (tmp_path / "systems" / "toy.py").write_text(TOY_SYSTEM)
    (tmp_path / "toy_ref.py").write_text(TOY_REF)
    monkeypatch.setattr(chipbench, "__path__", [*chipbench.__path__, str(tmp_path)])
    monkeypatch.setattr(systems, "__path__", [*systems.__path__, str(tmp_path / "systems")])
    importlib.invalidate_caches()
    yield tmp_path
    for name in ("chipbench.systems.toy", "chipbench.toy_ref"):
        sys.modules.pop(name, None)


def test_new_files_are_picked_up_without_edits(toy):
    from chipbench import systems

    wl = spec.load_workload("cell.x", toy)
    cfg = spec.load_config(wl["config"], toy)
    assert cfg["name"] == "cfg_x"
    assert spec.load_reader("metric.x", toy)(None) == 42.0
    # a system and a yardstick are found by the names the configuration gives
    cell = systems.build(cfg, wl, seed=1, seconds=1.0, devices=[], scratch=toy)
    assert cell.n == 5 and type(cell).__module__ == "chipbench.systems.toy"
    ref = spec.load_yardstick(cfg["reference"])
    assert ref is cell.ref and ref.flops_per_token({"n": 2}) == 12.0
    bench = {
        "end_to_end": [{"name": "setup_s"}, {"name": "a", "workloads": ["other"]}],
        "per_layer": [{"name": "metric.x", "moves": "setup_s"},
                      {"name": "y", "moves": "a"},
                      {"name": "z", "moves": "a", "workloads": ["cell.x"]}],
    }
    e2e, layer = spec.cell_metrics(bench, "cell.x")
    assert [m["name"] for m in e2e] == ["setup_s"]
    assert [m["name"] for m in layer] == ["metric.x", "z"]


def test_an_unknown_system_names_its_configuration(tmp_path):
    from chipbench import systems

    cfg = {"name": "cfg_y", "system": "no_such_system"}
    with pytest.raises(ValueError, match="cfg_y"):
        systems.build(cfg, {}, seed=1, seconds=1.0, devices=[], scratch=tmp_path)
    with pytest.raises(ModuleNotFoundError):
        spec.load_yardstick("no_such_ref")


def test_a_new_system_runs_through_the_harness_as_files_alone(toy, capsys):
    import jax

    from chipbench import harness

    bench = {
        "workloads": [{"name": "cell.x", "config": "cfg_x", "traffic": "toy", "chips": 1}],
        "end_to_end": [{"name": "latency_p50_us", "unit": "us"},
                       {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "toy_calls", "unit": "count", "moves": "latency_p50_us"},
                      {"name": "toy_flops", "unit": "FLOP", "moves": "latency_p50_us"}],
    }
    kw = dict(seed=3, seconds=1.0, devices=jax.devices()[:1], base=toy,
              peaks_of=lambda kind: peaks.PEAKS["TPU v5 lite"])
    out = harness.run_cell(bench, bench["workloads"][0], trace=False,
                           t_start=time.perf_counter(), **kw)
    assert out["correct"] and out["attempted"] == 5
    assert out["metrics"]["latency_p50_us"]["value"] == pytest.approx(1000.0)
    assert set(out["metrics"]) == {"latency_p50_us", "setup_s"}
    traced = harness.run_cell(bench, bench["workloads"][0], trace=True,
                              t_start=time.perf_counter(), **kw)
    assert traced["metrics"] == {"toy_calls": {"value": 5, "unit": "count"},
                                 "toy_flops": {"value": 12.0, "unit": "FLOP"}}
    ctl = harness.run_cell(bench, bench["workloads"][0], trace=False, control=True,
                           t_start=time.perf_counter(), **kw)
    assert not ctl["correct"]
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == ctl


def test_yardstick_work_is_the_work_functions_and_train_mfu_reads_it():
    from chipbench import harness, mamba_ref

    cfg = spec.load_config("mamba2_130m_train")
    m = cfg["model"]
    assert spec.load_yardstick(cfg["reference"]).flops_per_token(m) == \
        mamba_ref.flops_per_token(m) == peaks.mamba2_flops_per_token(m)
    peak = peaks.PEAKS["TPU v5 lite"]
    run = harness.Run(
        cell={"chips": 1}, workload={}, config=cfg, peaks=peak, seconds=20.0,
        setup_s=0.0, window=None, counters_before={}, counters_after={},
        e2e={"train_tokens_per_s": 27047.0},
    )
    want = 100.0 * 27047.0 * peaks.mamba2_flops_per_token(m) / peak["bf16_flops_per_s"]
    assert spec.load_reader("train_mfu")(run) == want


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
