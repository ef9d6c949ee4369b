"""Mixture-of-Experts with expert-parallel (EP) dispatch.

Production path (``_ep_moe``): runs inside ``shard_map`` with experts sharded
over the model axis. Dispatch is sort-based with static capacity:

  router top-k -> counts -> *exclusive prefix scan* for per-expert offsets
  (the paper's primitive, via the Pallas prefix-scan kernel path) ->
  scatter into (E, C, d) -> all_to_all -> expert FFN -> all_to_all back ->
  weighted combine.

Fallback path (``_dense_moe``): dropless einsum over all experts — used on
single-device smoke meshes and when E doesn't divide the model axis.

Aux losses (load-balance + router z-loss) are psum-averaged across the mesh.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import prefix_scan
from repro.models.layers import _ACT
from repro.sharding import current_topology

Params = Dict[str, Any]


def init_moe(key, cfg, dtype) -> Params:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.moe_num_experts
    ks = jax.random.split(key, 5)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(ff)
    p = {
        "router": jax.random.normal(ks[0], (d, E), jnp.float32) * s_in,
        "w_in": jax.random.normal(ks[1], (E, d, ff), dtype) * s_in,
        "w_gate": jax.random.normal(ks[2], (E, d, ff), dtype) * s_in,
        "w_out": jax.random.normal(ks[3], (E, ff, d), dtype) * s_out,
    }
    if cfg.moe_num_shared:
        sh_ff = cfg.moe_num_shared * ff
        km = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_in": jax.random.normal(km[0], (d, sh_ff), dtype) * s_in,
            "w_gate": jax.random.normal(km[1], (d, sh_ff), dtype) * s_in,
            "w_out": jax.random.normal(km[2], (sh_ff, d), dtype) * s_out,
        }
    if not cfg.gated_mlp:
        p.pop("w_gate")
        if "shared" in p:
            p["shared"].pop("w_gate")
    return p


def _router(logits: jax.Array, k: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (gates (n,k), experts (n,k), probs (n,E))."""
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = lax.top_k(probs, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, experts, probs


def _aux_losses(probs: jax.Array, experts: jax.Array, E: int,
                logits=None) -> Tuple[jax.Array, jax.Array]:
    """Switch-style load-balance loss + router z-loss (local means)."""
    n, k = experts.shape
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # (n,k,E)
    frac_tokens = onehot.sum((0, 1)) / (n * k)
    frac_probs = probs.mean(0)
    lb = E * jnp.sum(frac_tokens * frac_probs)
    zin = logits if logits is not None else jnp.log(probs + 1e-20)
    z = jnp.mean(jnp.square(jax.nn.logsumexp(zin, axis=-1)))
    return lb, z


def _expert_ffn(p: Params, x: jax.Array, act: str) -> jax.Array:
    """x: (E_loc, C', d) -> (E_loc, C', d)."""
    a = _ACT[act]
    h = jnp.einsum("ecd,edf->ecf", x, p["w_in"])
    if "w_gate" in p:
        h = a(jnp.einsum("ecd,edf->ecf", x, p["w_gate"])) * h
    else:
        h = a(h)
    return jnp.einsum("ecf,efd->ecd", h, p["w_out"])


def _shared_ffn(p: Params, x: jax.Array, act: str) -> jax.Array:
    a = _ACT[act]
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"])
    if "w_gate" in p:
        h = a(jnp.einsum("bsd,df->bsf", x, p["w_gate"])) * h
    else:
        h = a(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"])


def _dense_moe(p: Params, x: jax.Array, cfg, act: str):
    """Dropless reference path: every expert sees every token (masked)."""
    B, S, d = x.shape
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    xf = x.reshape(-1, d)
    logits = (xf.astype(jnp.float32) @ p["router"]).astype(jnp.float32)
    gates, experts, probs = _router(logits, k)
    lb, z = _aux_losses(probs, experts, E, logits)
    # combine weights (n, E)
    comb = jnp.zeros((xf.shape[0], E), x.dtype)
    comb = comb.at[jnp.arange(xf.shape[0])[:, None], experts].add(
        gates.astype(x.dtype)
    )
    h = jnp.einsum("nd,edf->nef", xf, p["w_in"])
    if "w_gate" in p:
        h = _ACT[act](jnp.einsum("nd,edf->nef", xf, p["w_gate"])) * h
    else:
        h = _ACT[act](h)
    y = jnp.einsum("nef,efd->ned", h, p["w_out"])
    out = jnp.einsum("ned,ne->nd", y, comb).reshape(B, S, d)
    if "shared" in p:
        out = out + _shared_ffn(p["shared"], x, act)
    return out, {"load_balance": lb, "router_z": z}


def _ep_region(x, router, w_in, w_gate, w_out, *, cfg, act, axis, ep, dp_axes):
    """Per-device EP dispatch. x: (B_loc, S_loc, d); experts sharded E_loc."""
    B, S, d = x.shape
    n = B * S
    E, k = cfg.moe_num_experts, cfg.moe_top_k
    C = int(math.ceil(n * k / E * cfg.capacity_factor))
    # round capacity to a lane multiple so the (E, C, d) buffer tiles cleanly
    C = max(8, -(-C // 8) * 8)

    xf = x.reshape(n, d)
    logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
    gates, experts, probs = _router(logits, k)
    # globally-exact aux stats: pmean the sufficient statistics FIRST
    axes = tuple(dp_axes) + (axis,)
    onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)
    frac_tokens = lax.pmean(onehot.sum((0, 1)) / (n * k), axes)
    frac_probs = lax.pmean(probs.mean(0), axes)
    lb = E * jnp.sum(frac_tokens * frac_probs)
    z = lax.pmean(
        jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))), axes
    )

    flat_e = experts.reshape(-1)                      # (nk,)
    flat_g = gates.reshape(-1).astype(x.dtype)
    nk = n * k
    counts = jnp.sum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32), axis=0)  # (E,)
    # per-expert offsets: THE PAPER'S PRIMITIVE — exclusive prefix scan
    starts = prefix_scan(counts[None, :], op="add", exclusive=True)[0]
    order = jnp.argsort(flat_e)
    pos_sorted = jnp.arange(nk, dtype=jnp.int32) - starts[flat_e[order]]
    pos = jnp.zeros((nk,), jnp.int32).at[order].set(pos_sorted)

    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
    slot = jnp.where(pos < C, flat_e * C + pos, E * C)  # OOB -> dropped
    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(
        xf[tok], mode="drop"
    )
    # all_to_all: expert-group i goes to device i; my experts' tokens arrive
    # concatenated along capacity: (E, C, d) -> (E_loc, ep*C, d)
    buf = buf.reshape(E, C, d)
    buf = lax.all_to_all(buf, axis, split_axis=0, concat_axis=1, tiled=True)

    ep_params = {"w_in": w_in, "w_out": w_out}
    if w_gate is not None:
        ep_params["w_gate"] = w_gate
    out = _expert_ffn(ep_params, buf, act)

    # reverse: (E_loc, ep*C, d) -> (E, C, d)
    out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0, tiled=True)
    out = out.reshape(E * C, d)
    got = out.at[slot].get(mode="fill", fill_value=0)  # (nk, d)
    y = jnp.zeros((n, d), x.dtype).at[tok].add(got * flat_g[:, None])
    return y.reshape(B, S, d), lb, z


def moe_block(p: Params, x: jax.Array, cfg, *, act: str = "silu"):
    """Top-level MoE FFN. Chooses EP (shard_map) or dense fallback."""
    topo = current_topology()
    E = cfg.moe_num_experts
    ep = topo.model_size
    if topo.mesh is None or ep == 1 or E % ep != 0:
        return _dense_moe(p, x, cfg, act)

    axis = topo.model_axis
    dp = topo.batch_axes
    B, S, d = x.shape
    gated = "w_gate" in p
    dpspec = dp[0] if len(dp) == 1 else dp

    # tokens: batch over dp; sequence over the model axis (SP) when it
    # divides, else fold the model axis into batch (decode), else replicate.
    dp_size = topo.dp_size
    if S % ep == 0 and B % dp_size == 0:
        x_spec = P(dpspec, axis, None)
    elif B % (dp_size * ep) == 0:
        x_spec = P(tuple(dp) + (axis,), None, None)
    elif B % dp_size == 0:
        x_spec = P(dpspec, None, None)
    else:
        x_spec = P(None, None, None)

    def region(x_l, router, w_in, w_gate, w_out):
        return _ep_region(
            x_l, router, w_in, w_gate, w_out,
            cfg=cfg, act=act, axis=axis, ep=ep, dp_axes=dp,
        )

    def region_plain(x_l, router, w_in, w_out):
        return _ep_region(
            x_l, router, w_in, None, w_out,
            cfg=cfg, act=act, axis=axis, ep=ep, dp_axes=dp,
        )

    w_spec = P(axis, None, None)
    if gated:
        mapped = shard_map(
            region,
            mesh=topo.mesh,
            in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
            out_specs=(x_spec, P(), P()),
            check_vma=False,
        )
        y, lb, z = mapped(x, p["router"], p["w_in"], p["w_gate"], p["w_out"])
    else:
        mapped = shard_map(
            region_plain,
            mesh=topo.mesh,
            in_specs=(x_spec, P(None, None), w_spec, w_spec),
            out_specs=(x_spec, P(), P()),
            check_vma=False,
        )
        y, lb, z = mapped(x, p["router"], p["w_in"], p["w_out"])
    if "shared" in p:
        y = y + _shared_ffn(p["shared"], x, act)
    return y, {"load_balance": lb, "router_z": z}
