"""Blocked prefix-scan Pallas kernel (the on-chip half of the paper's scan).

The paper offloads the *inter-node* scan to the NIC; the *intra-node* combine
ran in the NetFPGA datapath at line rate. On TPU the intra-device analogue is
this kernel: a VMEM-blocked scan along the last axis that streams HBM tiles
through VMEM exactly once, carrying the running prefix in a VMEM scratch
across sequential grid steps (the TPU grid's innermost dimension executes in
order on the TensorCore, so the scratch acts as the NIC's "partial sum
register").

Layout: rows are blocked to sublane multiples (8 for f32), the scan axis to
lane multiples (128). Each grid step loads one (BR, BL) tile, does an in-tile
Hillis–Steele scan on the VPU (log2(BL) lane rotations by ``pltpu.roll``, the
wrapped lanes masked to the identity by an iota compare), folds in the carry,
and updates the carry with the tile's last column — one HBM read + one HBM
write per element, the memory roofline for a scan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_IDENT = {
    "add": lambda dt: jnp.zeros((), dt),
    "max": lambda dt: (
        jnp.array(jnp.finfo(dt).min, dt)
        if jnp.issubdtype(dt, jnp.floating)
        else jnp.array(jnp.iinfo(dt).min, dt)
    ),
    "mul": lambda dt: jnp.ones((), dt),
}

_COMBINE = {
    "add": jnp.add,
    "max": jnp.maximum,
    "mul": jnp.multiply,
}


def lane_scan(x: jax.Array, combine, ident) -> jax.Array:
    """Inclusive scan along the lane axis (axis 1) of a 2-D tile.

    Hillis–Steele: round ``d`` combines every lane with lane ``i - d``,
    fetched by a lane rotation; the ``d`` lanes that wrap around take the
    identity instead. Mosaic lowers rotations and iota compares, which is
    why this stands in for ``lax.associative_scan`` (whose strided slices
    it refuses).
    """
    n = x.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    d = 1
    while d < n:
        prev = jnp.where(lane >= d, pltpu.roll(x, d, 1), ident)
        x = combine(prev, x)
        d *= 2
    return x


def _wide(dtype) -> jnp.dtype:
    """The 32-bit dtype a tile is scanned in: Mosaic rotates only 32-bit
    vectors, and legalizes few narrow-integer ops."""
    dtype = jnp.dtype(dtype)
    if dtype.itemsize >= 4:
        return dtype
    return jnp.dtype(
        jnp.float32 if jnp.issubdtype(dtype, jnp.floating) else jnp.int32
    )


def _scan_kernel(x_ref, o_ref, carry_ref, *, op: str):
    """One (BR, BL) tile: local scan + carry fold, carry update."""
    j = pl.program_id(1)
    combine = _COMBINE[op]
    ident = _IDENT[op](carry_ref.dtype)

    @pl.when(j == 0)
    def _init():
        carry_ref[...] = jnp.full_like(carry_ref, ident)

    x = x_ref[...].astype(carry_ref.dtype)
    local = lane_scan(x, combine, ident)
    carry = carry_ref[:, :1]  # (BR, 1) broadcasts over the tile
    out = combine(carry, local)
    o_ref[...] = out.astype(o_ref.dtype)
    carry_ref[:, :1] = out[:, -1:]


def prefix_scan_pallas(
    x: jax.Array,
    *,
    op: str = "add",
    block_rows: int = 256,
    block_len: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Inclusive scan along axis -1 of a 2D (R, L) array.

    R must divide by block_rows and L by block_len (ops.py pads).
    """
    if x.ndim != 2:
        raise ValueError(f"expected 2D (rows, length), got {x.shape}")
    rows, length = x.shape
    block_rows = min(block_rows, rows)
    block_len = min(block_len, length)
    if rows % block_rows or length % block_len:
        raise ValueError(
            f"shape {x.shape} not divisible by blocks ({block_rows},{block_len})"
        )
    grid = (rows // block_rows, length // block_len)
    kernel = functools.partial(_scan_kernel, op=op)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_len), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_len), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, 128), _wide(x.dtype))],
        interpret=interpret,
    )(x)
