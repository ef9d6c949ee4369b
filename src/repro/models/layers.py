"""Transformer substrate: norms, RoPE/M-RoPE, GQA attention (flash-blocked,
sliding-window, KV-cache decode, KV-sequence-sharded decode), gated MLP.

Everything is a pure function over explicit param pytrees (dicts of arrays);
activation sharding is expressed through ``repro.sharding.shard`` logical
annotations so the same code runs unsharded on 1 CPU device and fully sharded
on the production mesh.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map

from repro.perf_flags import FLAGS as _DEFAULT_FLAGS
from repro import perf_flags
from repro.sharding import current_topology, shard

Params = Dict[str, Any]


def tp_out_einsum(spec: str, a, b):
    """Projection einsum whose output crosses a TP psum.

    With tp_reduce_bf16, the dot emits bf16 directly so the partitioner's
    all-reduce carries bf16 (half the wire bytes); otherwise XLA's f32-accum
    lowering leaves the psum payload in f32 on this backend."""
    if perf_flags.FLAGS.tp_reduce_bf16:
        return jnp.einsum(
            spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            preferred_element_type=jnp.bfloat16,
        )
    return jnp.einsum(spec, a, b)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm(w: jax.Array, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * lax.rsqrt(var + eps)) * (1.0 + w.astype(jnp.float32))).astype(dt)


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)).astype(dt)


def norm(p, x, kind: str = "rmsnorm"):
    if kind == "rmsnorm":
        return rmsnorm(p["scale"], x)
    return layernorm(p, x)


def init_norm(d: int, kind: str, dtype) -> Params:
    if kind == "rmsnorm":
        return {"scale": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def _rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 1e4
) -> jax.Array:
    """x: (B, S, H, D); positions: (B, S) int32."""
    d = x.shape[-1]
    freqs = _rope_freqs(d, theta)  # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def mrope_sections(head_dim: int) -> tuple:
    """Qwen2-VL splits head_dim/2 freq slots 1:1.5:1.5 over (t, h, w) —
    (16, 24, 24) at head_dim=128; scaled proportionally otherwise."""
    half = head_dim // 2
    t = max(1, half // 4)
    h = (half - t) // 2
    return (t, h, half - t - h)


def apply_mrope(
    x: jax.Array,
    positions3: jax.Array,
    theta: float = 1e4,
    sections: tuple = None,
) -> jax.Array:
    """Qwen2-VL multimodal RoPE: positions3 (B, S, 3) = (t, h, w) streams.

    head_dim/2 frequency slots are split across the three position streams
    (sections sum to head_dim/2); text tokens carry t==h==w so M-RoPE reduces
    to 1-D RoPE for them.
    """
    d = x.shape[-1]
    if sections is None:
        sections = mrope_sections(d)
    assert sum(sections) == d // 2, (sections, d)
    freqs = _rope_freqs(d, theta)  # (d/2,)
    parts = []
    start = 0
    for i, sec in enumerate(sections):
        f = freqs[start : start + sec]
        ang = positions3[..., i][..., None].astype(jnp.float32) * f
        parts.append(ang)
        start += sec
    angles = jnp.concatenate(parts, axis=-1)  # (B, S, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def init_attention(key, cfg, dtype) -> Params:
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    h, kh = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    p = {
        "wq": jax.random.normal(k1, (d, h, hd), dtype) * s,
        "wk": jax.random.normal(k2, (d, kh, hd), dtype) * s,
        "wv": jax.random.normal(k3, (d, kh, hd), dtype) * s,
        "wo": jax.random.normal(k4, (h, hd, d), dtype) * s,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((kh, hd), dtype)
        p["bv"] = jnp.zeros((kh, hd), dtype)
    return p


def _qkv(p: Params, x: jax.Array, xkv: Optional[jax.Array] = None):
    xkv = x if xkv is None else xkv
    q = tp_out_einsum("bsd,dhk->bshk", x, p["wq"])
    k = tp_out_einsum("bsd,dhk->bshk", xkv, p["wk"])
    v = tp_out_einsum("bsd,dhk->bshk", xkv, p["wv"])
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return q, k, v


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: jax.Array | int = 0,
    q_offset: jax.Array | int = 0,
    q_block: int = 1024,
    kv_block: int = 1024,
    seq_shard: bool = False,
) -> jax.Array:
    """Memory-safe blocked attention (jnp flash): scan over KV blocks.

    q: (B, Sq, H, D); k/v: (B, Sk, Kh, D) with H = G*Kh (GQA). ``window`` > 0
    masks keys older than ``window`` positions (sliding-window attention);
    pass a traced scalar to select local/global per scanned layer.
    ``q_offset`` is the absolute position of q[0] (decode / sharded-sequence).

    The per-(q-block, kv-block) body is checkpointed so the backward pass
    recomputes scores instead of storing (B, H, Sq, Sk).
    """
    B, Sq, H, D = q.shape
    _, Sk, Kh, _ = k.shape
    G = H // Kh
    q_block = min(q_block, Sq)
    kv_block = min(kv_block, Sk)
    nq = -(-Sq // q_block)
    nk = -(-Sk // kv_block)
    # pad to block multiples
    qp = jnp.pad(q, ((0, 0), (0, nq * q_block - Sq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, nk * kv_block - Sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nk * kv_block - Sk), (0, 0), (0, 0)))
    scale = 1.0 / math.sqrt(D)
    qp = (qp * scale).reshape(B, nq, q_block, Kh, G, D)
    kp = kp.reshape(B, nk, kv_block, Kh, D)
    vp = vp.reshape(B, nk, kv_block, Kh, D)

    if seq_shard:
        # shard the query-block dim over the model axis: each device scores
        # q_block/msize queries against the (gathered) KV — removes the
        # replicated-attention waste when heads don't divide the axis
        qp = shard(qp, "batch", None, "seq", None, None, None)

    q_pos = q_offset + jnp.arange(nq * q_block).reshape(nq, q_block)
    k_pos = jnp.arange(nk * kv_block).reshape(nk, kv_block)
    k_valid = (jnp.arange(nk * kv_block) < Sk).reshape(nk, kv_block)

    @jax.checkpoint
    def block(qb, qpos, kb, vb, kpos, kval):
        # qb: (B, q_block, Kh, G, D); kb/vb: (B, kv_block, Kh, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb).astype(jnp.float32)
        mask = kval[None, None, None, None, :]
        if causal is not None and causal:
            mask = mask & (qpos[:, None] >= kpos[None, :])[None, None, None]
        w = window if isinstance(window, jax.Array) else jnp.array(window)
        win_mask = (qpos[:, None] - kpos[None, :]) < w
        mask = mask & jnp.where(w > 0, win_mask, True)[None, None, None]
        s = jnp.where(mask, s, -1e30)
        m = jnp.max(s, axis=-1)                          # (B,h,g,q)
        probs = jnp.exp(s - m[..., None])
        l = jnp.sum(probs, axis=-1)
        if perf_flags.FLAGS.attn_probs_bf16:
            o = jnp.einsum(
                "bhgqk,bkhd->bhgqd",
                probs.astype(jnp.bfloat16), vb.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            )
        else:
            o = jnp.einsum("bhgqk,bkhd->bhgqd", probs, vb.astype(jnp.float32))
        return m, l, o

    def q_loop(_, qi):
        qb, qpos = qi

        def kv_loop(carry, ki):
            m, l, o = carry
            kb, vb, kpos, kval = ki
            mb, lb, ob = block(qb, qpos, kb, vb, kpos, kval)
            mn = jnp.maximum(m, mb)
            c1 = jnp.exp(m - mn)
            c2 = jnp.exp(mb - mn)
            return (
                mn,
                l * c1 + lb * c2,
                o * c1[..., None] + ob * c2[..., None],
            ), None

        m0 = jnp.full((B, Kh, G, q_block), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Kh, G, q_block), jnp.float32)
        o0 = jnp.zeros((B, Kh, G, q_block, D), jnp.float32)
        (m, l, o), _ = lax.scan(
            kv_loop,
            (m0, l0, o0),
            (
                jnp.moveaxis(kp, 1, 0),
                jnp.moveaxis(vp, 1, 0),
                k_pos,
                k_valid,
            ),
        )
        out = o / jnp.maximum(l, 1e-30)[..., None]       # (B,h,g,q,D)
        return None, out

    _, outs = lax.scan(q_loop, None, (jnp.moveaxis(qp, 1, 0), q_pos))
    if seq_shard:
        outs = shard(outs, None, "batch", None, None, "seq", None)
    # outs: (nq, B, Kh, G, q_block, D) -> (B, Sq, H, D)
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Kh, G, nq * q_block, D)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, nq * q_block, H, D)
    return out[:, :Sq].astype(q.dtype)


def attention_block(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg,
    *,
    causal: bool = True,
    window: jax.Array | int = 0,
    xkv: Optional[jax.Array] = None,
    positions3: Optional[jax.Array] = None,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill). TP over heads when they
    divide the model axis, else sequence stays sharded and XLA gathers KV.

    With return_kv=True also returns the (roped-k, v) pair for decode caches.
    """
    topo = current_topology()
    seq_over_tp = perf_flags.FLAGS.attn_seq_over_tp
    if not seq_over_tp and _tp_ready(topo, cfg.num_heads):
        q, k, v = explicit_tp_qkv(p, x, xkv, topo)
    else:
        q, k, v = _qkv(p, x, xkv)
    if xkv is None:  # self-attention: rotate both q and k
        if positions3 is not None and cfg.mrope:
            q = apply_mrope(q, positions3, cfg.rope_theta)
            k = apply_mrope(k, positions3, cfg.rope_theta)
        elif cfg.rope_theta > 0:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
    msize = topo.model_size
    heads_ok = msize <= 1 or (q.shape[2] % msize == 0)
    blocks_ok = min(1024, q.shape[1]) % max(msize, 1) == 0
    seq_shard = (
        (perf_flags.FLAGS.seq_shard_attn and not heads_ok) or seq_over_tp
    ) and blocks_ok
    if not (seq_over_tp and blocks_ok):
        q = shard(q, "batch", None, "heads", None)
        k = shard(k, "batch", None, "heads", None)
        v = shard(v, "batch", None, "heads", None)
    out = flash_attention(
        q, k, v, causal=causal, window=window, seq_shard=seq_shard,
        kv_block=perf_flags.FLAGS.attn_kv_block,
    )
    if not seq_over_tp and _tp_ready(topo, cfg.num_heads):
        out = explicit_tp_wo(out, p["wo"], topo)
    else:
        out = tp_out_einsum("bshk,hkd->bsd", out, p["wo"])
    out = shard(out, "batch", None, None)
    if return_kv:
        return out, (k, v)
    return out


def decode_attention(
    p: Params,
    x: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    cfg,
    *,
    window: int = 0,
    update_cache: bool = True,
    positions3: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode vs a (B, S_max, Kh, D) KV cache.

    Returns (out, new_k_cache, new_v_cache). The new token is written at
    ``cache_len``. For cross-attention pass update_cache=False.
    """
    B, S_max, Kh, D = k_cache.shape
    q, k, v = _qkv(p, x)
    pos = jnp.full((B, 1), cache_len, jnp.int32)
    if positions3 is not None and cfg.mrope:
        q = apply_mrope(q, positions3, cfg.rope_theta)
        k = apply_mrope(k, positions3, cfg.rope_theta)
    elif cfg.rope_theta > 0:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if update_cache:
        k_cache = lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, cache_len, 0, 0)
        )
        v_cache = lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, cache_len, 0, 0)
        )
    H = cfg.num_heads
    G = H // Kh
    scale = 1.0 / math.sqrt(D)
    qh = (q * scale).reshape(B, Kh, G, D)
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qh, k_cache,
        preferred_element_type=jnp.float32,
    )
    kpos = jnp.arange(S_max)
    valid = kpos[None, None, None, :] <= cache_len
    w = window if isinstance(window, jax.Array) else jnp.array(window)
    win_ok = cache_len - kpos[None, None, None, :] < w
    valid = valid & jnp.where(w > 0, win_ok, True)
    s = jnp.where(valid, s, -1e30)
    attn_w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum(
        "bhgs,bshd->bhgd", attn_w.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    o = o.reshape(B, 1, H, D).astype(x.dtype)
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    return out, k_cache, v_cache


def seq_sharded_decode_attention_core(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    cfg,
    *,
    axis_name: str,
    window: jax.Array | int = 0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Decode attention with the KV cache sharded along SEQUENCE over
    ``axis_name`` (for archs whose kv-head count can't split the model axis:
    MQA granite, qwen2.5, jamba...).

    Runs inside shard_map; q/k/v are precomputed (and roped) outside so the
    projection weights keep their TP sharding. Each device scores its cache
    shard and the partial (m, l, o) triplets are merged with the associative
    flash combine via pmax/psum — the same operator algebra as the scan
    collective (core.operators.make_flash_op). The new token is written by
    the owner shard only. Returns the merged per-head outputs (B, 1, H, D);
    the wo projection happens outside.
    """
    B, S_shard, Kh, D = k_cache.shape
    idx = lax.axis_index(axis_name)
    # owner shard writes the new kv
    local_start = idx * S_shard
    off = cache_len - local_start
    owner = (off >= 0) & (off < S_shard)
    safe_off = jnp.clip(off, 0, S_shard - 1)
    new_k = lax.dynamic_update_slice(
        k_cache, k.astype(k_cache.dtype), (0, safe_off, 0, 0)
    )
    new_v = lax.dynamic_update_slice(
        v_cache, v.astype(v_cache.dtype), (0, safe_off, 0, 0)
    )
    k_cache = jnp.where(owner, new_k, k_cache)
    v_cache = jnp.where(owner, new_v, v_cache)

    H = cfg.num_heads
    G = H // Kh
    scale = 1.0 / math.sqrt(D)
    qh = (q * scale).reshape(B, Kh, G, D)
    s = jnp.einsum(
        "bhgd,bshd->bhgs", qh, k_cache,
        preferred_element_type=jnp.float32,
    )
    kpos = local_start + jnp.arange(S_shard)
    valid = kpos[None, None, None, :] <= cache_len
    w = window if isinstance(window, jax.Array) else jnp.array(window)
    win_ok = cache_len - kpos[None, None, None, :] < w
    valid = valid & jnp.where(w > 0, win_ok, True)
    s = jnp.where(valid, s, -1e30)
    m = jnp.max(s, axis=-1)
    l = jnp.sum(jnp.exp(s - m[..., None]), axis=-1)
    o = jnp.einsum(
        "bhgs,bshd->bhgd",
        jnp.exp(s - m[..., None]).astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    # associative flash merge across shards
    mg = lax.pmax(m, axis_name)
    c = jnp.exp(m - mg)
    lg = lax.psum(l * c, axis_name)
    og = lax.psum(o * c[..., None], axis_name)
    o = (og / jnp.maximum(lg, 1e-30)[..., None]).reshape(B, 1, H, D)
    return o.astype(q.dtype), k_cache, v_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------

_ACT = {"silu": jax.nn.silu, "gelu": partial(jax.nn.gelu, approximate=True), "relu": jax.nn.relu}


def init_mlp(key, d: int, ff: int, dtype, gated: bool = True) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(ff)
    p = {
        "w_in": jax.random.normal(k1, (d, ff), dtype) * s_in,
        "w_out": jax.random.normal(k2, (ff, d), dtype) * s_out,
    }
    if gated:
        p["w_gate"] = jax.random.normal(k3, (d, ff), dtype) * s_in
    return p


def mlp_block(p: Params, x: jax.Array, act: str = "silu") -> jax.Array:
    topo = current_topology()
    ff = p["w_in"].shape[-1]
    if _tp_ready(topo, ff):
        return explicit_tp_mlp(p, x, act, topo)
    a = _ACT[act]
    h = tp_out_einsum("bsd,df->bsf", x, p["w_in"])
    if "w_gate" in p:
        g = tp_out_einsum("bsd,df->bsf", x, p["w_gate"])
        h = a(g) * h
    else:
        h = a(h)
    h = shard(h, "batch", None, "ff")
    out = tp_out_einsum("bsf,fd->bsd", h, p["w_out"])
    return shard(out, "batch", None, None)


def decode_kv_mode(cfg) -> str:
    """Cache layout for decode: 'heads' when kv heads divide the model axis,
    'seq' (sequence-sharded cache + LSE psum merge) otherwise, 'local' off-mesh."""
    topo = current_topology()
    if topo.mesh is None or topo.model_size <= 1:
        return "local"
    return "heads" if cfg.num_kv_heads % topo.model_size == 0 else "seq"


def cached_attention(p, x, kc, vc, cache_len, cfg, *, window=0, kv_mode="local"):
    """One-token attention against a KV cache, dispatching on cache layout."""
    if kv_mode == "seq":
        from jax.sharding import PartitionSpec as P

        topo = current_topology()
        axis = topo.model_axis
        dp = topo.batch_axes
        B = x.shape[0]
        dpspec = dp[0] if len(dp) == 1 else dp
        bspec = dpspec if (B % topo.dp_size == 0 and B > 1) else None
        q, k, v = _qkv(p, x)
        pos = jnp.full((B, 1), cache_len, jnp.int32)
        if cfg.rope_theta > 0:
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)

        def region(q, k, v, kc, vc, clen, win):
            return seq_sharded_decode_attention_core(
                q, k, v, kc, vc, clen, cfg, axis_name=axis, window=win
            )

        cspec = P(bspec, axis, None, None)
        rspec = P(bspec, None, None, None)
        win_arr = window if isinstance(window, jax.Array) else jnp.array(window)
        o, kc, vc = shard_map(
            region,
            mesh=topo.mesh,
            in_specs=(rspec, rspec, rspec, cspec, cspec, P(), P()),
            out_specs=(rspec, cspec, cspec),
            check_vma=False,
        )(q, k, v, kc, vc, cache_len, win_arr)
        out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
        return out, kc, vc
    return decode_attention(p, x, kc, vc, cache_len, cfg, window=window)


# ---------------------------------------------------------------------------
# Explicit-TP projections (perf flag: explicit_tp)
#
# The GSPMD partitioner on this backend places TP all-reduces on the f32
# accumulation value (float-normalization runs first), doubling wire bytes.
# Running the projections inside shard_map puts the psum under OUR control:
# payload is cast to the activation dtype before it touches the wire — the
# same move the paper makes by taking the collective out of the generic MPI
# stack into the NIC. Autodiff of the region places the dx boundary psum on
# the primal dtype as well.
# ---------------------------------------------------------------------------


def _tp_ready(topo, *dims):
    return (
        perf_flags.FLAGS.explicit_tp
        and topo.mesh is not None
        and topo.model_size > 1
        and all(d % topo.model_size == 0 for d in dims)
    )


def _batch_spec_entry(topo, batch_dim: int):
    """DP sharding entry for a batch dim, or None when it can't shard."""
    if batch_dim % max(topo.dp_size, 1) != 0 or batch_dim <= 1:
        return None
    dp = topo.batch_axes
    return dp[0] if len(dp) == 1 else dp


def explicit_tp_mlp(p: Params, x: jax.Array, act: str, topo) -> jax.Array:
    """Gated MLP with explicit ff-sharded compute + owned bf16 psum."""
    from jax.sharding import PartitionSpec as P

    axis = topo.model_axis
    dpspec = _batch_spec_entry(topo, x.shape[0])
    a = _ACT[act]
    gated = "w_gate" in p

    def region(x_l, w_in, w_gate, w_out):
        h = jnp.einsum("bsd,df->bsf", x_l, w_in)
        if w_gate is not None:
            h = a(jnp.einsum("bsd,df->bsf", x_l, w_gate)) * h
        else:
            h = a(h)
        out = jnp.einsum("bsf,fd->bsd", h, w_out)
        # barrier pins the bf16 value so the wire payload stays narrow
        out = lax.optimization_barrier(out.astype(x_l.dtype))
        return lax.psum(out, axis)

    xspec = P(dpspec, None, None)
    if gated:
        fn = shard_map(
            region, mesh=topo.mesh,
            in_specs=(xspec, P(None, axis), P(None, axis), P(axis, None)),
            out_specs=xspec, check_vma=False,
        )
        return fn(x, p["w_in"], p["w_gate"], p["w_out"])
    fn = shard_map(
        lambda x_l, wi, wo: region(x_l, wi, None, wo),
        mesh=topo.mesh,
        in_specs=(xspec, P(None, axis), P(axis, None)),
        out_specs=xspec, check_vma=False,
    )
    return fn(x, p["w_in"], p["w_out"])


def explicit_tp_qkv(p: Params, x: jax.Array, xkv: Optional[jax.Array], topo):
    """Head-sharded q/k/v projections inside shard_map (dx psum owned)."""
    from jax.sharding import PartitionSpec as P

    axis = topo.model_axis
    msize = topo.model_size
    dpspec = _batch_spec_entry(topo, x.shape[0])
    kv_sharded = p["wk"].shape[1] % msize == 0
    has_bias = "bq" in p

    def region(x_l, xkv_l, wq, wk, wv, bq, bk, bv):
        q = jnp.einsum("bsd,dhk->bshk", x_l, wq)
        k = jnp.einsum("bsd,dhk->bshk", xkv_l, wk)
        v = jnp.einsum("bsd,dhk->bshk", xkv_l, wv)
        if bq is not None:
            q = q + bq
            k = k + bk
            v = v + bv
        return q, k, v

    xspec = P(dpspec, None, None)
    hspec = P(None, axis, None)
    kvspec = hspec if kv_sharded else P(None, None, None)
    hbspec = P(axis, None)
    kvbspec = hbspec if kv_sharded else P(None, None)
    out_h = P(dpspec, None, axis, None)
    out_kv = out_h if kv_sharded else P(dpspec, None, None, None)

    if has_bias:
        fn = shard_map(
            region, mesh=topo.mesh,
            in_specs=(xspec, xspec, hspec, kvspec, kvspec, hbspec, kvbspec, kvbspec),
            out_specs=(out_h, out_kv, out_kv), check_vma=False,
        )
        return fn(x, xkv if xkv is not None else x, p["wq"], p["wk"], p["wv"],
                  p["bq"], p["bk"], p["bv"])
    fn = shard_map(
        lambda x_l, xkv_l, wq, wk, wv: region(x_l, xkv_l, wq, wk, wv, None, None, None),
        mesh=topo.mesh,
        in_specs=(xspec, xspec, hspec, kvspec, kvspec),
        out_specs=(out_h, out_kv, out_kv), check_vma=False,
    )
    return fn(x, xkv if xkv is not None else x, p["wq"], p["wk"], p["wv"])


def explicit_tp_wo(out_heads: jax.Array, wo: jax.Array, topo) -> jax.Array:
    """Out-projection contraction over sharded heads with owned bf16 psum."""
    from jax.sharding import PartitionSpec as P

    axis = topo.model_axis
    dpspec = _batch_spec_entry(topo, out_heads.shape[0])

    def region(o_l, w_l):
        r = jnp.einsum("bshk,hkd->bsd", o_l, w_l)
        r = lax.optimization_barrier(r.astype(o_l.dtype))
        return lax.psum(r, axis)

    fn = shard_map(
        region, mesh=topo.mesh,
        in_specs=(P(dpspec, None, axis, None), P(axis, None, None)),
        out_specs=P(dpspec, None, None), check_vma=False,
    )
    return fn(out_heads, wo)
