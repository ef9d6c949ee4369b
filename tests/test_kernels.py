"""Pallas kernel validation: shape/dtype sweeps vs the jnp oracles.

Kernels execute in interpret mode on CPU (the TPU lowering is exercised by
the same pallas_call with interpret=False on real hardware).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.ops import prefix_scan, ssd_scan

SHAPES_2D = [(1, 1), (4, 100), (16, 512), (3, 257), (8, 128), (2, 1000)]
SHAPES_ND = [(2, 3, 64), (1, 2, 2, 130)]


@pytest.mark.parametrize("shape", SHAPES_2D + SHAPES_ND)
@pytest.mark.parametrize("op", ["add", "max", "mul"])
@pytest.mark.parametrize("exclusive", [False, True])
def test_prefix_scan_shapes(shape, op, exclusive):
    rng = np.random.default_rng(hash((shape, op)) % 2**31)
    if op == "mul":
        x = rng.uniform(0.5, 1.5, size=shape).astype(np.float32)
    else:
        x = rng.normal(size=shape).astype(np.float32)
    want = np.asarray(ref.ref_prefix_scan(jnp.asarray(x), op, exclusive=exclusive))
    got = np.asarray(
        prefix_scan(jnp.asarray(x), op=op, exclusive=exclusive, force_pallas=True)
    )
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_prefix_scan_dtypes(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 256)), dtype)
    got = prefix_scan(x, op="add", force_pallas=True)
    want = ref.ref_prefix_scan(x, "add")
    # bf16 running sums accumulate ~eps*sqrt(L) relative error and the
    # kernel's blocked association order differs from cumsum's
    tol = 1e-4 if dtype == jnp.float32 else 2.5e-1
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("blocks", [(8, 128), (16, 256), (256, 512)])
def test_prefix_scan_block_shapes(blocks):
    """Block-shape sweep: result must be block-size invariant."""
    br, bl = blocks
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(32, 1024)).astype(np.float32))
    got = prefix_scan(x, op="add", force_pallas=True, block_rows=br, block_len=bl)
    want = ref.ref_prefix_scan(x, "add")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)


@settings(max_examples=20, deadline=None)
@given(
    rows=st.integers(1, 20),
    length=st.integers(1, 300),
    seed=st.integers(0, 2**16),
)
def test_prefix_scan_property(rows, length, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, length)).astype(np.float32))
    got = prefix_scan(x, op="add", force_pallas=True)
    np.testing.assert_allclose(
        np.asarray(got), np.cumsum(np.asarray(x), -1), atol=1e-3, rtol=1e-3
    )


@pytest.mark.parametrize("shape", [(2, 64, 8), (1, 300, 4), (3, 128, 16), (1, 1, 2)])
@pytest.mark.parametrize("with_h0", [False, True])
def test_ssd_scan(shape, with_h0):
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.uniform(0.6, 1.0, size=shape).astype(np.float32))
    b = jnp.asarray(rng.normal(size=shape).astype(np.float32))
    h0 = (
        jnp.asarray(rng.normal(size=shape[:-2] + shape[-1:]).astype(np.float32))
        if with_h0
        else None
    )
    wh, wl = ref.ref_ssd_scan(a, b, h0)
    gh, gl = ssd_scan(a, b, h0, force_pallas=True)
    np.testing.assert_allclose(np.asarray(gh), np.asarray(wh), atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(gl), np.asarray(wl), atol=2e-3, rtol=2e-3)


def test_ssd_scan_sequential_oracle():
    """ref_ssd_scan itself vs a plain python loop (oracle-of-the-oracle)."""
    rng = np.random.default_rng(7)
    a = rng.uniform(0.5, 1.0, size=(2, 37, 3)).astype(np.float32)
    b = rng.normal(size=(2, 37, 3)).astype(np.float32)
    h = np.zeros((2, 3), np.float32)
    hs = []
    for t in range(37):
        h = a[:, t] * h + b[:, t]
        hs.append(h.copy())
    want = np.stack(hs, axis=1)
    got, _ = ref.ref_ssd_scan(jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)


# ---------------------------------------------------------------------------
# flash attention kernel
# ---------------------------------------------------------------------------
from repro.kernels.ops import flash_attention  # noqa: E402


@pytest.mark.parametrize("shape", [(2, 128, 128, 64), (1, 100, 260, 32),
                                   (3, 256, 256, 128), (2, 1, 300, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shapes(shape, causal):
    BH, Sq, Skv, D = shape
    rng = np.random.default_rng(hash((shape, causal)) % 2**31)
    q = jnp.asarray(rng.normal(size=(BH, Sq, D)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(BH, Skv, D)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(BH, Skv, D)).astype(np.float32))
    off = Skv - Sq if causal and Skv >= Sq else 0
    want = np.asarray(ref.ref_flash_attention(q, k, v, causal=causal, q_offset=off))
    got = np.asarray(flash_attention(q, k, v, causal=causal, q_offset=off,
                                     force_pallas=True))
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_window(window):
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(2, 128, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 128, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 128, 64)).astype(np.float32))
    want = np.asarray(ref.ref_flash_attention(q, k, v, causal=True, window=window))
    got = np.asarray(flash_attention(q, k, v, causal=True, window=window,
                                     force_pallas=True))
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_flash_attention_block_invariance():
    rng = np.random.default_rng(10)
    q = jnp.asarray(rng.normal(size=(1, 256, 64)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 256, 64)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 256, 64)).astype(np.float32))
    a = np.asarray(flash_attention(q, k, v, force_pallas=True, block_q=64, block_kv=64))
    b = np.asarray(flash_attention(q, k, v, force_pallas=True, block_q=128, block_kv=256))
    np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)
