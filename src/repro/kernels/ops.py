"""Public jit'd wrappers over the Pallas kernels.

Dispatch policy: on TPU the Pallas kernels run natively; elsewhere (the CPU
backend) the default is the jnp reference (identical semantics & FLOPs) so
that full-model compiles stay tractable, and ``force_pallas=True`` (or env
REPRO_FORCE_PALLAS=1) routes through the kernels in interpret mode — that is
how the kernel test-suite executes them.

The wrappers own the ugly parts: shape flattening, padding to tile multiples,
and exclusive-shift handling, so kernels stay minimal. The additive prefix
scan carries a custom VJP whose backward pass runs the same kernel on
time-reversed input, so ``jax.grad`` of a model step goes through the kernel
on the chip too.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.prefix_scan import prefix_scan_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas

_IDENT_VAL = {"add": 0, "max": None, "mul": 1}  # max identity filled per-dtype


def _use_pallas(force_pallas: bool | None) -> tuple[bool, bool]:
    """(use_pallas, interpret)."""
    if force_pallas is None:
        force_pallas = os.environ.get("REPRO_FORCE_PALLAS", "0") == "1"
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        return True, False
    return (True, True) if force_pallas else (False, True)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _cumsum_2d(x, block_rows, block_len, interpret):
    return prefix_scan_pallas(
        x, op="add", block_rows=block_rows, block_len=block_len,
        interpret=interpret,
    )


def _cumsum_2d_fwd(x, block_rows, block_len, interpret):
    return _cumsum_2d(x, block_rows, block_len, interpret), None


def _cumsum_2d_bwd(block_rows, block_len, interpret, _, g):
    # d/dx_j of sum_{i>=j} g_i: a reverse cumulative sum
    rev = _cumsum_2d(g[:, ::-1], block_rows, block_len, interpret)
    return (rev[:, ::-1],)


_cumsum_2d.defvjp(_cumsum_2d_fwd, _cumsum_2d_bwd)


def _pad_to(x: jax.Array, mult: int, axis: int, fill) -> tuple[jax.Array, int]:
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad, constant_values=fill), n


@partial(jax.jit, static_argnames=("op", "exclusive", "force_pallas", "block_rows", "block_len"))
def prefix_scan(
    x: jax.Array,
    *,
    op: str = "add",
    exclusive: bool = False,
    force_pallas: bool | None = None,
    block_rows: int = 256,
    block_len: int = 512,
) -> jax.Array:
    """Prefix scan along the last axis of an arbitrary-rank array."""
    use, interpret = _use_pallas(force_pallas)
    if not use:
        return ref.ref_prefix_scan(x, op, exclusive=exclusive)

    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    if op == "max":
        fill = (
            jnp.finfo(x.dtype).min
            if jnp.issubdtype(x.dtype, jnp.floating)
            else jnp.iinfo(x.dtype).min
        )
    else:
        fill = _IDENT_VAL[op]
    # rows pad with identity (harmless), length pad with identity (trimmed)
    sub = 8 if x.dtype != jnp.int8 else 32
    flat, rows = _pad_to(flat, min(block_rows, max(sub, 1)), 0, fill)
    flat, length = _pad_to(flat, 128, 1, fill)
    br = min(block_rows, flat.shape[0])
    bl = min(block_len, flat.shape[1])
    # shrink blocks to divisors
    while flat.shape[0] % br:
        br //= 2
    while flat.shape[1] % bl:
        bl //= 2
    if op == "add":
        out = _cumsum_2d(flat, br, bl, interpret)
    else:
        out = prefix_scan_pallas(
            flat, op=op, block_rows=br, block_len=bl, interpret=interpret
        )
    out = out[:rows, :length].reshape(shape)
    if exclusive:
        ident = fill
        pad = jnp.full_like(out[..., :1], ident)
        out = jnp.concatenate([pad, out[..., :-1]], axis=-1)
    return out


@partial(jax.jit, static_argnames=("force_pallas", "block_rows", "block_time"))
def ssd_scan(
    a: jax.Array,
    b: jax.Array,
    h0: jax.Array | None = None,
    *,
    force_pallas: bool | None = None,
    block_rows: int = 256,
    block_time: int = 512,
) -> tuple[jax.Array, jax.Array]:
    """Diagonal recurrence h_t = a_t h_{t-1} + b_t along axis -2 of (..., T, D).

    Returns (h, h_last) with h: (..., T, D), h_last: (..., D).
    """
    use, interpret = _use_pallas(force_pallas)
    if not use:
        return ref.ref_ssd_scan(a, b, h0)

    shape = a.shape
    t, d = shape[-2], shape[-1]
    # kernel wants (rows, T): move time last, flatten the rest
    a2 = jnp.moveaxis(a, -2, -1).reshape(-1, t)
    b2 = jnp.moveaxis(b, -2, -1).reshape(-1, t)
    a2, rows = _pad_to(a2, 8, 0, 1.0)   # identity decay
    b2, _ = _pad_to(b2, 8, 0, 0.0)
    a2, tlen = _pad_to(a2, 128, 1, 1.0)
    b2, _ = _pad_to(b2, 128, 1, 0.0)
    br = min(block_rows, a2.shape[0])
    bt = min(block_time, a2.shape[1])
    while a2.shape[0] % br:
        br //= 2
    while a2.shape[1] % bt:
        bt //= 2
    h2, _ = ssd_scan_pallas(
        a2, b2, block_rows=br, block_time=bt, interpret=interpret
    )
    h2 = h2[:rows, :tlen]
    h = jnp.moveaxis(h2.reshape(shape[:-2] + (d, t)), -1, -2)
    if h0 is not None:
        # fold initial state: h_t += A_t * h0 with A_t the running decay prod
        A2 = prefix_scan(
            jnp.moveaxis(a, -2, -1), op="mul", force_pallas=force_pallas
        )
        A = jnp.moveaxis(A2, -1, -2)
        h = h + A * h0[..., None, :]
    return h, h[..., -1, :]


@partial(jax.jit, static_argnames=("causal", "window", "q_offset",
                                   "force_pallas", "block_q", "block_kv"))
def flash_attention(
    q: jax.Array,      # (BH, Sq, D)
    k: jax.Array,      # (BH, Skv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    force_pallas: bool | None = None,
    block_q: int = 128,
    block_kv: int = 128,
) -> jax.Array:
    """Flash attention over flattened (batch*heads, seq, head_dim) operands.

    Pads seq dims to block multiples; padded KV columns are masked via
    kv_len, padded queries are trimmed.
    """
    use, interpret = _use_pallas(force_pallas)
    if not use:
        return ref.ref_flash_attention(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    BH, Sq, D = q.shape
    _, Skv, _ = k.shape
    qp, _ = _pad_to(q, min(block_q, max(Sq, 1)), 1, 0)
    kp, _ = _pad_to(k, min(block_kv, max(Skv, 1)), 1, 0)
    vp, _ = _pad_to(v, min(block_kv, max(Skv, 1)), 1, 0)
    bq = min(block_q, qp.shape[1])
    bkv = min(block_kv, kp.shape[1])
    while qp.shape[1] % bq:
        bq //= 2
    while kp.shape[1] % bkv:
        bkv //= 2
    out = flash_attention_pallas(
        qp, kp, vp, causal=causal, window=window, q_offset=q_offset,
        kv_len=Skv, block_q=bq, block_kv=bkv, interpret=interpret,
    )
    return out[:, :Sq]
