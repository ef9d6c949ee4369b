"""Data pipeline, optimizer, checkpoint, compression, straggler unit tests."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.data.pipeline import DataConfig, batches, pack_documents
from repro.checkpoint.manager import CheckpointManager
from repro.optim.adamw import AdamWConfig, adamw_update, init_opt_state, lr_at
from repro.optim.compression import (
    compress_with_feedback,
    dequantize_int8,
    quantize_int8,
)
from repro.runtime.straggler import StragglerDetector
from repro.runtime.fault import plan_remesh, rescale_batch


# ------------------------------------------------------------------- data
def test_pack_documents_offsets():
    docs = [np.arange(2, 7, dtype=np.int32), np.arange(10, 13, dtype=np.int32)]
    packed, seg = pack_documents(docs, seq_len=4, pad_id=0)
    flat = packed.reshape(-1)
    assert list(flat[:5]) == [2, 3, 4, 5, 6]
    assert list(flat[5:8]) == [10, 11, 12]
    assert (seg.reshape(-1)[:5] == 1).all()
    assert (seg.reshape(-1)[5:8] == 2).all()


def test_batches_deterministic_and_sharded():
    cfg = DataConfig(vocab_size=1000, seq_len=64, global_batch=8, seed=7)
    b1 = next(batches(cfg))
    b2 = next(batches(cfg))
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (8, 64)
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])
    # different hosts -> different data
    cfg2 = DataConfig(
        vocab_size=1000, seq_len=64, global_batch=8, seed=7,
        host_id=1, host_count=2,
    )
    b3 = next(batches(cfg2))
    assert b3["tokens"].shape == (4, 64)
    assert not np.array_equal(b1["tokens"][:4], b3["tokens"])


# -------------------------------------------------------------- optimizer
def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=5, total_steps=200, weight_decay=0.0)
    target = jnp.asarray([1.0, -2.0, 3.0])
    params = {"w": jnp.zeros(3)}
    opt = init_opt_state(params)

    def loss_fn(p):
        return jnp.sum((p["w"] - target) ** 2)

    for _ in range(150):
        g = jax.grad(loss_fn)(params)
        params, opt, stats = adamw_update(g, opt, params, cfg)
    assert float(loss_fn(params)) < 1e-2
    assert np.isfinite(stats["grad_norm"])


def test_lr_schedule_shape():
    cfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=100, min_lr_ratio=0.1)
    lrs = [float(lr_at(jnp.asarray(s), cfg)) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 1e-3) < 1e-9          # peak at warmup end
    assert lrs[-1] <= lrs[1]
    assert lrs[-1] >= 0.1 * 1e-3 - 1e-12      # floor


def test_grad_clipping_applied():
    cfg = AdamWConfig(lr=1e-3, clip_norm=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(4)}
    opt = init_opt_state(params)
    huge = {"w": jnp.full(4, 1e6)}
    _, _, stats = adamw_update(huge, opt, params, cfg)
    assert float(stats["grad_norm"]) > 1e5  # raw norm reported pre-clip


# ------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip_and_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones(4, jnp.int32)}}
    for step in (1, 2, 3, 4):
        mgr.save(step, jax.tree.map(lambda x, s=step: x + s, tree))
    assert mgr.all_steps() == [3, 4]  # keep=2 GC'd older
    step, restored = mgr.restore(tree)
    assert step == 4
    np.testing.assert_allclose(np.asarray(restored["a"]), np.asarray(tree["a"]) + 4)


def test_checkpoint_async_then_restore(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_write=True)
    tree = {"w": jnp.ones((16, 16))}
    mgr.save(10, tree)
    mgr.wait()
    step, restored = mgr.restore(tree)
    assert step == 10
    np.testing.assert_allclose(np.asarray(restored["w"]), 1.0)


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.ones(3)}
    mgr.save(5, tree)
    # a crashed partial write leaves only .tmp — must be invisible
    (tmp_path / ".tmp_step_9").mkdir()
    assert mgr.latest_step() == 5


# ------------------------------------------------------------ compression
@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**16), scale=st.floats(1e-3, 1e3))
def test_quantize_roundtrip_error_bound(seed, scale):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32) * scale)
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s) - x))
    amax = float(jnp.max(jnp.abs(x)))
    assert err.max() <= amax / 127.0 + 1e-6


def test_error_feedback_preserves_signal():
    """With EF, repeated compression of a constant gradient is unbiased:
    the accumulated transmitted value converges to the true gradient."""
    g = {"w": jnp.asarray([0.001, 0.5, -0.3])}
    err = None
    sent = np.zeros(3)
    for _ in range(64):
        q, s, err = compress_with_feedback(g, err)
        sent += np.asarray(dequantize_int8(q["w"], s["w"]))
    np.testing.assert_allclose(sent / 64, np.asarray(g["w"]), atol=2e-3)


# --------------------------------------------------------------- runtime
def test_straggler_detector_flags_spikes():
    det = StragglerDetector(warmup=2, threshold=2.0, evict_after=2)
    verdicts = []
    times = [1.0, 1.0, 1.0, 1.0, 1.0, 5.0, 5.0, 1.0]
    for i, t in enumerate(times):
        verdicts.append(det.observe(i, t))
    assert verdicts[5]["flagged"] and verdicts[6]["flagged"]
    assert verdicts[6]["evict"]
    assert not verdicts[7]["flagged"]


def test_plan_remesh():
    assert plan_remesh(16, 16, lost_hosts=1) == (8, 16)
    assert plan_remesh(16, 16, lost_hosts=0) == (16, 16)
    assert plan_remesh(2, 16, lost_hosts=2) is None
    assert rescale_batch(256, 16, 8) == 128
