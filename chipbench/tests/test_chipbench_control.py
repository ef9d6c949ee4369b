"""The control and the faults, each run through the harness on the CPU at
a size a test run holds: every one has to come out as not correct, through
the same comparison and result line as the program's runs.

* the control: the reference computed in the next narrower type (int16 for
  int32, bfloat16 for float32; float8 for the bfloat16 model) in the
  program's place;
* offload cells: an answer altered where it is produced, and the exchange
  between ranks left out (every rank keeps its own contribution);
* the training cell: a step that returns its state unchanged, and half of
  the batch left out with the mean taken over the rest.

The sound program passes the same comparison, and a traced trainer passes
on the program's spans from inside the window.
"""

import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from chipbench import harness, peaks, spec, systems  # noqa: E402

SEED = 2**33 + 11


def _files(cell_name, workload=None, config=None):
    """The cell's workload and configuration, with some values replaced."""
    wl = dict(spec.load_workload(cell_name), **(workload or {}))
    cfg = spec.load_config(wl["config"])
    for k, v in (config or {}).items():
        cfg[k] = dict(cfg.get(k, {}), **v) if isinstance(v, dict) else v
    return wl, cfg


def _run(cell_name, *, workload=None, config=None, control=False, seconds=1.0):
    bench = spec.load_benchmark()
    cell = spec.cell(bench, cell_name)
    wl, cfg = _files(cell_name, workload, config)
    return harness.run_cell(
        bench, cell, seed=SEED, seconds=seconds, trace=False,
        devices=jax.devices()[:1], t_start=time.perf_counter(), workload=wl,
        config=cfg, peaks_of=lambda kind: peaks.PEAKS["TPU v5 lite"],
        control=control,
    )


BROKER_SMALL = dict(
    workload={"rate_per_s": 150, "tenants": 8, "bytes_per_rank": [4, 64]},
    config={"max_coalesce": 4},
)
ENGINE_SMALL = dict(workload={"bytes_per_rank": [4096], "check_sample": 8})
CELLS = {"broker.zipf_small": BROKER_SMALL, "engine.scan_64mib": ENGINE_SMALL}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_offload_control_fails_and_the_program_passes(cell):
    out = _run(cell, **CELLS[cell])
    assert out["correct"] and out["checks"]["wrong_answers"]["value"] == 0
    ctl = _run(cell, control=True, **CELLS[cell])
    assert not ctl["correct"] and ctl["checks"]["wrong_answers"]["value"] > 0


def _altered(orig):
    def offload(self, desc, x=None, *a, **k):
        out = orig(self, desc, x, *a, **k)
        return jax.tree.map(lambda o: o.at[(0,) * o.ndim].add(1), out)
    return offload


def _no_exchange(orig):
    def offload(self, desc, x=None, *a, **k):
        orig(self, desc, x, *a, **k)
        return x
    return offload


@pytest.mark.parametrize("fault", [_altered, _no_exchange])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_offload_faults_are_not_correct(cell, fault, monkeypatch):
    from repro.offload.engine import OffloadEngine

    monkeypatch.setattr(OffloadEngine, "offload", fault(OffloadEngine.offload))
    out = _run(cell, **CELLS[cell])
    assert not out["correct"] and out["checks"]["wrong_answers"]["value"] > 0


# At this size the program runs in float32 like the reference, so its
# readings are ~1e-7; the limits below sit far above them.
TRAIN_SMALL = dict(
    workload={"batch": 4, "seq_len": 64, "mean_doc_len": 32},
    config={"model": {"n_layer": 2, "d_model": 64, "vocab_size": 256,
                      "padded_vocab_size": 256, "d_state": 16, "headdim": 16,
                      "chunk_size": 16, "dtype": "float32"},
            "limits": {"loss_gap_step2": 1e-5, "grad_norm_gap_median": 1e-4,
                       "change_norm_gap": 1e-2}},
)


@pytest.fixture
def small_mamba(monkeypatch):
    from repro.configs import get_config
    from repro.launch import train

    small = dataclasses.replace(
        get_config("mamba2-130m"), num_layers=2, d_model=64, vocab_size=256,
        ssm_state=16, ssm_head_dim=16, ssm_chunk=16, dtype="float32")
    monkeypatch.setattr(train, "get_config", lambda name: small)


def test_train_control_fails_and_the_program_passes(small_mamba):
    out = _run("train.mamba2_130m", **TRAIN_SMALL)
    assert out["correct"], out["checks"]
    ctl = _run("train.mamba2_130m", control=True, **TRAIN_SMALL)
    assert not ctl["correct"], ctl["checks"]


def _unchanged(build):
    def wrapped(api, *a, **k):
        step, shapes, specs = build(api, *a, **k)

        def frozen(params, opt, batch):
            return params, opt, {"loss": api.loss(params, batch)[0]}
        return jax.jit(frozen), shapes, specs
    return wrapped


def _half_batch(build):
    def wrapped(api, *a, **k):
        step, shapes, specs = build(api, *a, **k)

        def half(params, opt, batch):
            n = batch["tokens"].shape[0] // 2
            return step(params, opt, {key: v[:n] for key, v in batch.items()})
        return half, shapes, specs
    return wrapped


@pytest.mark.parametrize("fault", [_unchanged, _half_batch])
def test_train_faults_are_not_correct(fault, small_mamba, monkeypatch):
    from repro.runtime import train_loop

    monkeypatch.setattr(train_loop, "build_train_step", fault(train_loop.build_train_step))
    out = _run("train.mamba2_130m", **TRAIN_SMALL)
    assert not out["correct"], out["checks"]


def _spanned(build):
    """A step that records a span of its own, as a program's model code may."""
    def wrapped(api, *a, **k):
        from repro.obs import tracing

        step, shapes, specs = build(api, *a, **k)

        def traced(params, opt, batch):
            with tracing.get_tracer().span("model.step", "model"):
                return step(params, opt, batch)
        return traced, shapes, specs
    return wrapped


def test_traced_trainer_passes_on_the_programs_spans_in_the_window(small_mamba, monkeypatch):
    from repro.obs import tracing
    from repro.runtime import train_loop

    monkeypatch.setattr(train_loop, "build_train_step", _spanned(train_loop.build_train_step))
    wl, cfg = _files("train.mamba2_130m", **TRAIN_SMALL)
    prev = tracing.set_tracer(None)  # the cell installs a fresh tracer
    try:
        cell = systems.build(cfg, wl, seed=SEED, seconds=0.5, devices=jax.devices()[:1],
                             scratch=harness.SCRATCH, tracing=True)
        cell.setup()
        win = cell.window()
        spans = cell.spans()
        every = tracing.get_tracer().spans()
    finally:
        tracing.set_tracer(prev)
    lo, hi = win.start * 1e6, win.end * 1e6
    # the set-up's steps recorded spans too; only the window's are passed on
    assert len(every) == len(spans) + wl["setup_steps"]
    assert [s.name for s in spans] == ["model.step"] * win.attempted
    assert all(lo <= s.start_us <= hi for s in spans)
