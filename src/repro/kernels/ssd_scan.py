"""Chunked diagonal-SSM scan Pallas kernel (Mamba2 SSD intra-chunk engine).

Computes h_t = a_t * h_{t-1} + b_t along time for (B, T, D) operands, blocked
over (batch-rows, time). The (decay-product, state) pair carry lives in VMEM
scratch across the sequential time-block grid steps — the same
carry-in-a-register structure the NetFPGA used to stream partial sums, and the
intra-device complement of ``core.dist_scan``'s inter-device SSD operator: the
model layer computes chunk-local trajectories with this kernel, then stitches
chunks across devices with the offloaded scan collective.

Time is mapped to the TPU *lane* axis within a tile (contiguous, 128-aligned)
and the (batch×feature) rows to sublanes; the in-tile pair scan is a
log2(tile) Hillis–Steele ladder of lane rotations (``pltpu.roll``) and
multiply-adds on the VPU, the wrapped lanes masked to the pair identity
(1, 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lane_pair_scan(a, b):
    """Inclusive scan of the pairs ``(a, b)`` under ``(al, bl) . (ar, br) =
    (ar * al, ar * bl + br)`` along the lane axis of 2-D tiles, in f32
    (Mosaic rotates only 32-bit vectors)."""
    if a.dtype != jnp.float32 or b.dtype != jnp.float32:
        A, B = _lane_pair_scan(a.astype(jnp.float32), b.astype(jnp.float32))
        return A.astype(a.dtype), B.astype(b.dtype)
    n = a.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, a.shape, 1)
    d = 1
    while d < n:
        keep = lane >= d
        a_prev = jnp.where(keep, pltpu.roll(a, d, 1), 1.0)
        b_prev = jnp.where(keep, pltpu.roll(b, d, 1), 0.0)
        a, b = a * a_prev, a * b_prev + b
        d *= 2
    return a, b


def _ssd_kernel(a_ref, b_ref, h_ref, acc_ref):
    """One (BR, BT) tile of rows x time. acc holds (a_prod, h) carries."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[0, ...] = jnp.ones_like(acc_ref[0])
        acc_ref[1, ...] = jnp.zeros_like(acc_ref[1])

    a = a_ref[...]
    b = b_ref[...]
    # In-tile inclusive pair scan along time (axis 1).
    A, B = _lane_pair_scan(a, b)
    a_in = acc_ref[0, :, :1]
    h_in = acc_ref[1, :, :1]
    # Fold in carry: h_t = B_t + A_t * h_in ; decay product also accumulates.
    h = B + A * h_in
    h_ref[...] = h
    acc_ref[0, :, :1] = A[:, -1:] * a_in
    acc_ref[1, :, :1] = h[:, -1:]


def ssd_scan_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block_rows: int = 256,
    block_time: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Diagonal recurrence along axis -1 of 2D (rows, T) operands.

    Returns (h, h_last). rows = flattened (batch x feature); callers reshape.
    """
    if a.shape != b.shape or a.ndim != 2:
        raise ValueError(f"expected matching 2D shapes, got {a.shape} {b.shape}")
    rows, t = a.shape
    block_rows = min(block_rows, rows)
    block_time = min(block_time, t)
    if rows % block_rows or t % block_time:
        raise ValueError(
            f"shape {a.shape} not divisible by blocks ({block_rows},{block_time})"
        )
    grid = (rows // block_rows, t // block_time)
    h = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_time), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, block_time), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_time), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(a.shape, b.dtype),
        scratch_shapes=[pltpu.VMEM((2, block_rows, 128), b.dtype)],
        interpret=interpret,
    )(a, b)
    return h, h[:, -1]
