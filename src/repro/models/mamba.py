"""Mamba2 (SSD) mixer with sequence-parallel inter-chunk scan.

TPU-native adaptation of SSD (state-space duality, arXiv:2405.21060):
  * intra-chunk work is the matmul ("attention-like") form — MXU-aligned
    einsums over (Q x Q) chunk score matrices;
  * within-chunk cumulative log-decays go through the Pallas prefix-scan
    kernel path (kernels.ops.prefix_scan);
  * inter-chunk state propagation h' = A*h + B is an associative scan:
    locally a lax.associative_scan over the chunk axis, and ACROSS DEVICES —
    when the sequence is sharded (seq_parallel) — the paper's offloaded scan
    collective ``core.dist_exscan`` with the SSD operator;
  * the causal depthwise conv's cross-shard halo is a single neighbor
    ppermute (rank 0's zero-fill is exactly causal padding).

Projections are stored per-segment (z, x, BC, dt) instead of one fused
in_proj so tensor-parallel sharding is clean: z/x shard the inner dim, dt the
head dim, BC stays replicated (it is tiny and shared across heads).

Modes:
  seq_parallel=True  — sequence sharded over the model axis (mamba2-130m;
                       weights replicated, the scan collective carries state).
  seq_parallel=False — heads sharded over the model axis (jamba-52b TP;
                       full sequence per device, scan stays local).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro import perf_flags
from repro.core import SSD, dist_exscan
from repro.kernels.ops import prefix_scan
from repro.sharding import current_topology, shard

Params = Dict[str, Any]

_CONV_WIDTH = 4


def init_mamba(key, cfg, dtype) -> Params:
    d = cfg.d_model
    di = cfg.ssm_d_inner
    N = cfg.ssm_state
    H = cfg.ssm_num_heads
    ks = jax.random.split(key, 6)
    s = 1.0 / math.sqrt(d)
    return {
        "w_z": jax.random.normal(ks[0], (d, di), dtype) * s,
        "w_x": jax.random.normal(ks[1], (d, di), dtype) * s,
        "w_bc": jax.random.normal(ks[2], (d, 2 * N), dtype) * s,
        "w_dt": jax.random.normal(ks[3], (d, H), dtype) * s,
        "conv_w_x": jax.random.normal(ks[4], (_CONV_WIDTH, di), dtype) * 0.5,
        "conv_b_x": jnp.zeros((di,), dtype),
        "conv_w_bc": jax.random.normal(ks[5], (_CONV_WIDTH, 2 * N), dtype) * 0.5,
        "conv_b_bc": jnp.zeros((2 * N,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H, dtype=jnp.float32)),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), math.log(math.e - 1), jnp.float32),
        "norm_scale": jnp.zeros((di,), dtype),
        "w_out": jax.random.normal(ks[0], (di, d), dtype) / math.sqrt(di),
    }


def _conv1d(x: jax.Array, w: jax.Array, b: jax.Array, halo: Optional[jax.Array]):
    """Depthwise causal conv width 4 + silu. halo: (B, 3, C) left context."""
    B, S, C = x.shape
    if halo is None:
        halo = jnp.zeros((B, _CONV_WIDTH - 1, C), x.dtype)
    ext = jnp.concatenate([halo, x], axis=1)
    out = jnp.zeros_like(x)
    for wi in range(_CONV_WIDTH):
        out = out + ext[:, wi : wi + S] * w[wi]
    return jax.nn.silu(out + b)


def _gated_rmsnorm(scale: jax.Array, y: jax.Array, z: jax.Array) -> jax.Array:
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * lax.rsqrt(var + 1e-6) * (1.0 + scale.astype(jnp.float32))).astype(y.dtype)


def _ssd_chunked(
    xs: jax.Array,     # (B, S, H, P) conv'd inputs
    Bc: jax.Array,     # (B, S, N)
    Cc: jax.Array,     # (B, S, N)
    dA: jax.Array,     # (B, S, H) log-decay increments (<= 0)
    dt: jax.Array,     # (B, S, H) softplus'd step sizes
    chunk: int,
    state_in: Optional[Tuple[jax.Array, jax.Array]] = None,
):
    """Chunked SSD. Returns (y, (A_tot, S_tot), extras) where extras enable a
    cheap post-hoc fold of a device-incoming state (SP mode)."""
    B, S, H, Pd = xs.shape
    N = Bc.shape[-1]
    Q = min(chunk, S)
    nc = S // Q
    assert S % Q == 0, (S, Q)

    xb = (xs * dt[..., None]).astype(xs.dtype)       # dt-scaled inputs
    xbc_ = xb.reshape(B, nc, Q, H, Pd)
    Bcc = Bc.reshape(B, nc, Q, N)
    Ccc = Cc.reshape(B, nc, Q, N)
    dAc = dA.reshape(B, nc, Q, H)

    # within-chunk cumulative log decay — Pallas prefix-scan path
    seg = prefix_scan(
        jnp.moveaxis(dAc, 2, 3).astype(jnp.float32)  # (B,nc,H,Q)
    )
    seg = jnp.moveaxis(seg, 3, 2)                    # (B,nc,Q,H)

    @jax.checkpoint
    def intra(Ccc, Bcc, seg, xbc_):
        scores = jnp.einsum("bcin,bcjn->bcij", Ccc, Bcc)
        Lmat = jnp.exp(
            jnp.clip(seg[:, :, :, None, :] - seg[:, :, None, :, :], -60.0, 0.0)
        )  # (B,c,i,j,H)
        ii = jnp.arange(Q)
        causal = (ii[:, None] >= ii[None, :]).astype(scores.dtype)
        W = scores[..., None] * Lmat * causal[None, None, :, :, None]
        return jnp.einsum("bcijh,bcjhp->bcihp", W, xbc_)

    y_intra = intra(Ccc, Bcc, seg, xbc_)

    # chunk summary states: S_c = sum_j decay_to_end_j * xb_j (x) B_j
    decay_end = jnp.exp(seg[:, :, -1:, :] - seg)     # (B,c,Q,H)
    S_c = jnp.einsum("bcjhp,bcjn->bchpn", xbc_ * decay_end[..., None], Bcc)
    A_c = jnp.exp(seg[:, :, -1, :])                  # (B,c,H)

    # local inclusive scan over the chunk axis
    def comb(l, r):
        al, sl = l
        ar, sr = r
        return ar * al, ar[..., None, None] * sl + sr

    A_inc, S_inc = lax.associative_scan(comb, (A_c, S_c), axis=1)
    A_exc = jnp.concatenate([jnp.ones_like(A_inc[:, :1]), A_inc[:, :-1]], axis=1)
    S_exc = jnp.concatenate([jnp.zeros_like(S_inc[:, :1]), S_inc[:, :-1]], axis=1)
    if state_in is not None:
        a_in, s_in = state_in                        # (B,H), (B,H,P,N)
        S_exc = A_exc[..., None, None] * s_in[:, None] + S_exc
        A_exc = A_exc * a_in[:, None]

    y_inter = jnp.einsum("bcin,bchpn->bcihp", Ccc, S_exc) * jnp.exp(seg)[..., None]
    y = (y_intra + y_inter).reshape(B, S, H, Pd)
    A_tot, S_tot = A_inc[:, -1], S_inc[:, -1]        # device totals
    if state_in is not None:
        S_tot = A_inc[:, -1][..., None, None] * state_in[1] + S_tot
        A_tot = A_tot * state_in[0]
    extras = (Ccc, seg, A_exc)
    return y, (A_tot, S_tot), extras


def _project(p: Params, x: jax.Array, cfg, halo_x: Optional[jax.Array], tp: bool):
    """proj + conv. Returns (z, xs, Bc, Cc, dtp, dA)."""
    B, S, _ = x.shape
    di, N, H = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
    Pd = cfg.ssm_head_dim
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    x_in = jnp.einsum("bsd,de->bse", x, p["w_x"])
    bc = jnp.einsum("bsd,de->bse", x, p["w_bc"])
    dt = jnp.einsum("bsd,de->bse", x, p["w_dt"])
    if tp:
        z = shard(z, "batch", None, "model")
        x_in = shard(x_in, "batch", None, "model")
        dt = shard(dt, "batch", None, "heads")

    halo_xin = halo_bc = None
    if halo_x is not None:
        halo_xin = jnp.einsum("bsd,de->bse", halo_x, p["w_x"])
        halo_bc = jnp.einsum("bsd,de->bse", halo_x, p["w_bc"])
    tails = (x_in[:, -(_CONV_WIDTH - 1):], bc[:, -(_CONV_WIDTH - 1):])
    x_in = _conv1d(x_in, p["conv_w_x"], p["conv_b_x"], halo_xin)
    bc = _conv1d(bc, p["conv_w_bc"], p["conv_b_bc"], halo_bc)
    xs = x_in.reshape(B, S, H, Pd)
    Bc, Cc = bc[..., :N], bc[..., N:]
    dtp = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    dA = dtp * A
    return z, xs, Bc, Cc, dtp, dA, tails


def _mixer_core(p: Params, x: jax.Array, cfg, halo_x, state_in, seq_axis, tp):
    B, S, _ = x.shape
    di = cfg.ssm_d_inner
    H, Pd = cfg.ssm_num_heads, cfg.ssm_head_dim
    chunk = perf_flags.FLAGS.ssm_chunk or cfg.ssm_chunk
    z, xs, Bc, Cc, dtp, dA, tails = _project(p, x, cfg, halo_x, tp)

    if seq_axis is not None:
        y, (A_tot, S_tot), (Ccc, seg, A_exc) = _ssd_chunked(
            xs, Bc, Cc, dA, dtp, chunk
        )
        # cross-device incoming state via the offloaded scan collective
        payload = (A_tot[..., None, None], S_tot)
        if perf_flags.FLAGS.scan_payload_bf16:
            # barrier pins the narrow dtype so converts can't hoist across
            # the ppermutes (wire payload stays bf16)
            payload = lax.optimization_barrier(
                jax.tree.map(lambda t: t.astype(jnp.bfloat16), payload)
            )
        a_in, s_in = dist_exscan(
            payload, SSD, seq_axis,
            algorithm=perf_flags.FLAGS.scan_algorithm,
        )
        a_in = a_in[..., 0, 0].astype(A_tot.dtype)   # (B,H)
        s_in = s_in.astype(S_tot.dtype)
        y_add = jnp.einsum(
            "bcin,bch,bhpn->bcihp", Ccc, A_exc, s_in
        ) * jnp.exp(seg)[..., None]
        y = y + y_add.reshape(B, S, H, Pd)
        S_tot = A_tot[..., None, None] * s_in + S_tot
        A_tot = A_tot * a_in
    else:
        y, (A_tot, S_tot), _ = _ssd_chunked(
            xs, Bc, Cc, dA, dtp, chunk, state_in=state_in
        )

    y = y + p["D"][None, None, :, None].astype(y.dtype) * xs.astype(y.dtype)
    y = y.reshape(B, S, di)
    y = _gated_rmsnorm(p["norm_scale"], y.astype(x.dtype), z)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"]).astype(x.dtype)
    if tp:
        out = shard(out, "batch", None, None)
    cache = {
        "ssm": S_tot.astype(jnp.float32),
        "conv_x": tails[0],
        "conv_bc": tails[1],
    }
    if seq_axis is not None:
        # decode cache is global: take the LAST sequence shard's values
        psize = lax.axis_size(seq_axis)
        last = lax.axis_index(seq_axis) == psize - 1
        cache = jax.tree.map(
            lambda a: lax.psum(jnp.where(last, a, jnp.zeros_like(a)), seq_axis),
            cache,
        )
    return out, cache


def mamba_mixer(
    p: Params,
    x: jax.Array,
    cfg,
    *,
    seq_parallel: bool = False,
    state_in: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence SSD mixer (train / prefill).

    Returns (y, cache) where cache = {ssm, conv_x, conv_bc} is decode-ready
    (the final SSD state and the conv-input tails)."""
    topo = current_topology()
    B, S, _ = x.shape
    sp_ok = (
        seq_parallel
        and topo.mesh is not None
        and topo.model_size > 1
        and S % topo.model_size == 0
        and (S // topo.model_size) >= _CONV_WIDTH
    )
    if not sp_ok:
        tp = topo.mesh is not None and not seq_parallel
        return _mixer_core(p, x, cfg, None, state_in, None, tp)

    axis = topo.model_axis
    dp = topo.batch_axes
    dpspec = dp[0] if len(dp) == 1 else dp
    x_spec = P(dpspec, axis, None)
    wspecs = jax.tree.map(lambda _: P(), p)

    def region(p_l, x_l):
        # conv halo: last 3 raw tokens from the left sequence shard (rank 0
        # receives ppermute zero-fill == causal zero padding)
        psize = lax.axis_size(axis)
        tail = x_l[:, -(_CONV_WIDTH - 1):, :]
        halo_x = lax.ppermute(tail, axis, [(i, i + 1) for i in range(psize - 1)])
        return _mixer_core(p_l, x_l, cfg, halo_x, None, axis, False)

    cache_specs = {
        "ssm": P(dpspec, None, None, None),
        "conv_x": P(dpspec, None, None),
        "conv_bc": P(dpspec, None, None),
    }
    mapped = shard_map(
        region,
        mesh=topo.mesh,
        in_specs=(wspecs, x_spec),
        out_specs=(x_spec, cache_specs),
        check_vma=False,
    )
    return mapped(p, x)


def init_mamba_state(cfg, batch: int, dtype=jnp.float32):
    H, Pd, N = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state
    return {
        "ssm": jnp.zeros((batch, H, Pd, N), dtype),
        "conv_x": jnp.zeros((batch, _CONV_WIDTH - 1, cfg.ssm_d_inner), dtype),
        "conv_bc": jnp.zeros((batch, _CONV_WIDTH - 1, 2 * N), dtype),
    }


def mamba_decode(
    p: Params, x: jax.Array, state: Dict[str, jax.Array], cfg
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Single-token SSD step. x: (B, 1, d); state: {ssm, conv_x, conv_bc}."""
    B = x.shape[0]
    di, N, H = cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_num_heads
    Pd = cfg.ssm_head_dim
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    x_in = jnp.einsum("bsd,de->bse", x, p["w_x"])
    bc = jnp.einsum("bsd,de->bse", x, p["w_bc"])
    dt = jnp.einsum("bsd,de->bse", x, p["w_dt"])

    ext_x = jnp.concatenate([state["conv_x"], x_in], axis=1)    # (B, W, di)
    ext_bc = jnp.concatenate([state["conv_bc"], bc], axis=1)
    cx = jax.nn.silu(jnp.einsum("bwc,wc->bc", ext_x, p["conv_w_x"]) + p["conv_b_x"])
    cbc = jax.nn.silu(jnp.einsum("bwc,wc->bc", ext_bc, p["conv_w_bc"]) + p["conv_b_bc"])

    xs = cx.reshape(B, H, Pd)
    Bc, Cc = cbc[..., :N], cbc[..., N:]
    dtp = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    decay = jnp.exp(dtp * A)                          # (B,H)
    h = state["ssm"]
    h = (
        decay[..., None, None] * h
        + (dtp[..., None] * xs.astype(jnp.float32))[..., None]
        * Bc.astype(jnp.float32)[:, None, None, :]
    )
    y = jnp.einsum("bhpn,bn->bhp", h, Cc.astype(jnp.float32))
    y = y + p["D"][None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, 1, di).astype(x.dtype)
    y = _gated_rmsnorm(p["norm_scale"], y, z)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"]).astype(x.dtype)
    return out, {"ssm": h, "conv_x": ext_x[:, 1:], "conv_bc": ext_bc[:, 1:]}
