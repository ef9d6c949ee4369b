"""Plain float32 reference of the Mamba2 language model the program trains:
its weights from a seed, its loss and gradients, and AdamW.

The mixer follows Mamba2's SSD in its chunked dual form, as the minimal
listing of arXiv 2405.21060 (``ssd_minimal_discrete``, with the stable
segment sum) writes it, with one B/C group. Every contraction is a float32
``einsum`` at ``Precision.HIGHEST``; nothing here imports the program.

Where the program departs from the published Mamba2 block, this reference
follows the program, because it checks the program's arithmetic:

* four in-projections (z, x, B|C, dt) instead of one fused matrix, and two
  depthwise causal convolutions (x, and B|C) instead of one over xBC, which
  computes the same function;
* RMSNorm weights are ``1 + scale`` with ``scale`` starting at zero, and the
  norm's epsilon is the configuration's ``norm_eps``;
* no dt clamp;
* the tied LM head runs over the padded vocabulary, pad rows included in
  the softmax.

``fp8``, passed as ``q``, replaces each contraction's operands by their
float8 rounding with a per-tensor scale (e4m3, and e5m2 for cotangents):
the control, the reference computed in the next precision below the
configuration's bfloat16.

This is the yardstick that ``configs/mamba2_130m_train.json`` names
(``"reference": "mamba_ref"``); ``systems/trainer.py`` reads everything
model-specific from it by the names ``spec`` lists.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from chipbench import peaks

Params = Dict[str, Any]
HIGHEST = lax.Precision.HIGHEST
F32 = jnp.float32

#: where the program's model config (the key's field) must agree with the
#: configuration's ``model`` (the value's key)
PROGRAM_FIELDS = {
    "num_layers": "n_layer", "d_model": "d_model", "vocab_size": "vocab_size",
    "padded_vocab": "padded_vocab_size", "ssm_state": "d_state",
    "ssm_head_dim": "headdim", "ssm_expand": "expand",
    "ssm_chunk": "chunk_size", "tie_embeddings": "tie_embeddings",
    "dtype": "dtype",
}

#: limits of the numbers compared, each between the sound program's
#: readings and the control's or a fault's (PERF.md gives them). Read and
#: not compared: step 1's and step 3's loss gaps and the worst leaf's
#: gradient gap (PERF.md says why).
LIMITS = {"loss_gap_step2": 0.025, "grad_norm_gap_median": 0.0022,
          "change_norm_gap": 0.4}

#: leaf names under ``blocks/mamba`` in the order keys are drawn
MIXER_LEAVES = (
    "w_z", "w_x", "w_bc", "w_dt", "conv_w_x", "conv_b_x", "conv_w_bc",
    "conv_b_bc", "A_log", "D", "dt_bias", "norm_scale", "w_out",
)


def dims(m: Dict[str, Any]) -> Dict[str, int]:
    d = m["d_model"]
    di = m["expand"] * d
    return dict(d=d, di=di, N=m["d_state"], P=m["headdim"], H=di // m["headdim"],
                L=m["n_layer"], Vp=m["padded_vocab_size"], K=m["d_conv"],
                Q=m["chunk_size"])


def param_shapes(m: Dict[str, Any]) -> Params:
    """The weights' tree, shapes and types (the layout the program uses)."""
    g = dims(m)
    d, di, N, H, L, K = g["d"], g["di"], g["N"], g["H"], g["L"], g["K"]
    wt = jnp.bfloat16 if m["dtype"] == "bfloat16" else F32
    s = jax.ShapeDtypeStruct
    mixer = {
        "w_z": s((L, d, di), wt), "w_x": s((L, d, di), wt),
        "w_bc": s((L, d, 2 * N), wt), "w_dt": s((L, d, H), wt),
        "conv_w_x": s((L, K, di), wt), "conv_b_x": s((L, di), wt),
        "conv_w_bc": s((L, K, 2 * N), wt), "conv_b_bc": s((L, 2 * N), wt),
        "A_log": s((L, H), F32), "D": s((L, H), F32), "dt_bias": s((L, H), F32),
        "norm_scale": s((L, di), wt), "w_out": s((L, di, d), wt),
    }
    return {
        "embed": s((g["Vp"], d), wt),
        "final_norm": {"scale": s((d,), wt)},
        "blocks": {"norm1": {"scale": s((L, d), wt)}, "mamba": mixer},
    }


def init_params(key, m: Dict[str, Any]) -> Params:
    """Weights drawn from ``key`` as the published Mamba2 initialises them
    (``mamba_ssm``: ``Mamba2`` and ``_init_weights`` with
    ``rescale_prenorm_residual``): projections uniform in +-1/sqrt(fan-in),
    the out-projection then divided by sqrt(n_layer); conv weights and
    biases uniform in +-1/sqrt(d_conv); ``dt`` log-uniform in [1e-3, 1e-1]
    stored as its inverse softplus; ``A_log = log U(1, 16)``; ``D = 1``;
    norm weights 1 (scale 0); embedding normal, std 0.02."""
    g = dims(m)
    shapes = param_shapes(m)
    mix = shapes["blocks"]["mamba"]
    keys = dict(zip(MIXER_LEAVES + ("embed",), jax.random.split(key, len(MIXER_LEAVES) + 1)))

    def uniform(name, bound):
        sd = mix[name]
        return jax.random.uniform(keys[name], sd.shape, F32, -bound, bound).astype(sd.dtype)

    d, di, K, L = g["d"], g["di"], g["K"], g["L"]
    out = {name: uniform(name, 1 / math.sqrt(d)) for name in ("w_z", "w_x", "w_bc", "w_dt")}
    out["w_out"] = (uniform("w_out", 1 / math.sqrt(di)).astype(F32) / math.sqrt(L)).astype(
        mix["w_out"].dtype)
    for name in ("conv_w_x", "conv_b_x", "conv_w_bc", "conv_b_bc"):
        out[name] = uniform(name, 1 / math.sqrt(K))
    shape = mix["dt_bias"].shape
    dt = jnp.exp(jax.random.uniform(keys["dt_bias"], shape, F32, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    out["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    out["A_log"] = jnp.log(jax.random.uniform(keys["A_log"], shape, F32, 1.0, 16.0))
    out["D"] = jnp.ones(shape, F32)
    out["norm_scale"] = jnp.zeros(mix["norm_scale"].shape, mix["norm_scale"].dtype)
    zeros = lambda sd: jnp.zeros(sd.shape, sd.dtype)  # noqa: E731
    return {
        "embed": (jax.random.normal(keys["embed"], shapes["embed"].shape, F32) * 0.02).astype(
            shapes["embed"].dtype),
        "final_norm": {"scale": zeros(shapes["final_norm"]["scale"])},
        "blocks": {"norm1": {"scale": zeros(shapes["blocks"]["norm1"]["scale"])},
                   "mamba": out},
    }


# ------------------------------------------------------------------ model


def _round8(x, dtype, top: float):
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / top, 1.0)
    return (x / s).astype(dtype).astype(F32) * s


@jax.custom_vjp
def fp8(x):
    """float8 rounding with a per-tensor scale, as float8 training does it:
    e4m3 on the way forward, e5m2 for the cotangent on the way back."""
    return _round8(x, jnp.float8_e4m3fn, 448.0)


fp8.defvjp(lambda x: (fp8(x), None),
           lambda _, g: (_round8(g, jnp.float8_e5m2, 57344.0),))

#: the control's rounding of every contraction's operands
control = fp8


def _ein(q: Optional[Callable]):
    def ein(spec: str, *ops):
        if q is not None:
            ops = tuple(q(o) for o in ops)
        return jnp.einsum(spec, *ops, precision=HIGHEST,
                          preferred_element_type=F32)
    return ein


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * (1.0 + scale)


def _conv(x, w, b):
    """Depthwise causal convolution + SiLU; x (B, S, C), w (K, C)."""
    K, S = w.shape[0], x.shape[1]
    ext = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(ext[:, k : k + S] * w[k] for k in range(K))
    return jax.nn.silu(out + b)


def _segsum(x):
    """Stable segment sum: out[..., i, j] = sum(x[..., j+1 : i+1]) for
    i >= j, -inf above the diagonal."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    out = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), out, -jnp.inf)


def ssd(X, A, B, C, Q: int, ein):
    """SSD over chunks of ``Q``. X (b, S, H, P) dt-scaled inputs, A (b, S, H)
    log decays, B and C (b, S, N). Returns y (b, S, H, P)."""
    b, S, H, P = X.shape
    N = B.shape[-1]
    c = S // Q
    X = X.reshape(b, c, Q, H, P)
    A = A.reshape(b, c, Q, H).transpose(0, 3, 1, 2)          # b h c l
    B = B.reshape(b, c, Q, N)
    C = C.reshape(b, c, Q, N)
    A_cum = jnp.cumsum(A, axis=-1)
    Lmat = jnp.exp(_segsum(A))                                # b h c l s
    scores = ein("bcln,bcsn->bcls", C, B)
    y_diag = ein("bhcls,bcshp->bclhp", Lmat * scores[:, None], X)
    decay_states = jnp.exp(A_cum[..., -1:] - A_cum)           # b h c l
    states = ein("bcln,bclhp->bchpn", B, X * decay_states.transpose(0, 2, 3, 1)[..., None])
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    decay_chunk = jnp.exp(_segsum(jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
    states = ein("bhzc,bchpn->bzhpn", decay_chunk, states)[:, :-1]
    y_off = ein("bcln,bchpn->bclhp", C, states) * jnp.exp(A_cum).transpose(0, 2, 3, 1)[..., None]
    return (y_diag + y_off).reshape(b, S, H, P)


def _mixer(p: Params, h, g: Dict[str, int], eps: float, ein):
    b, S, _ = h.shape
    H, P, N = g["H"], g["P"], g["N"]
    z = ein("bsd,de->bse", h, p["w_z"])
    xin = _conv(ein("bsd,de->bse", h, p["w_x"]), p["conv_w_x"], p["conv_b_x"])
    bc = _conv(ein("bsd,de->bse", h, p["w_bc"]), p["conv_w_bc"], p["conv_b_bc"])
    dt = jax.nn.softplus(ein("bsd,de->bse", h, p["w_dt"]) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    xs = xin.reshape(b, S, H, P)
    y = ssd(xs * dt[..., None], dt * A, bc[..., :N], bc[..., N:], g["Q"], ein)
    y = y + p["D"][:, None] * xs
    y = _rms(y.reshape(b, S, g["di"]) * jax.nn.silu(z), p["norm_scale"], eps)
    return ein("bse,ed->bsd", y, p["w_out"])


def loss_sum(params: Params, tokens, labels, m: Dict[str, Any], q=None):
    """Summed next-token cross-entropy of a block of rows."""
    g = dims(m)
    eps = float(m["norm_eps"])
    ein = _ein(q)
    params = jax.tree.map(lambda a: a.astype(F32), params)
    x = params["embed"][tokens]

    @jax.checkpoint
    def layer(x, p):
        return x + _mixer(p["mamba"], _rms(x, p["norm1"]["scale"], eps), g, eps, ein), None

    x, _ = lax.scan(layer, x, params["blocks"])
    x = _rms(x, params["final_norm"]["scale"], eps)
    logits = ein("bsd,vd->bsv", x, params["embed"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - ll)


# -------------------------------------------------------------- training


def lr_at(step: int, o: Dict[str, Any]) -> float:
    """Linear warm-up, then cosine decay to ``min_lr_ratio`` of the peak."""
    if step < o["warmup_steps"]:
        return o["lr"] * step / max(o["warmup_steps"], 1)
    prog = min(1.0, max(0.0, (step - o["warmup_steps"])
                        / max(o["total_steps"] - o["warmup_steps"], 1)))
    return o["lr"] * (o["min_lr_ratio"]
                      + (1 - o["min_lr_ratio"]) * 0.5 * (1 + math.cos(math.pi * prog)))


class Reference:
    """Three (or more) AdamW steps of the plain model from given weights,
    gradients accumulated over blocks of rows."""

    def __init__(self, m: Dict[str, Any], o: Dict[str, Any], rows_per_block: int,
                 q: Optional[Callable] = None):
        self.m, self.o, self.rows = m, o, int(rows_per_block)
        self._grad = jax.jit(jax.value_and_grad(
            lambda p, t, l: loss_sum(p, t, l, m, q)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._adam = jax.jit(self._adam_step, static_argnums=())

    def _adam_step(self, master, grads, mom, vel, count, lr):
        o = self.o
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1c = 1 - o["b1"] ** count
        b2c = 1 - o["b2"] ** count
        mom = jax.tree.map(lambda a, g: o["b1"] * a + (1 - o["b1"]) * g, mom, grads)
        vel = jax.tree.map(lambda a, g: o["b2"] * a + (1 - o["b2"]) * g * g, vel, grads)
        master = jax.tree.map(
            lambda w, a, v: w - lr * ((a / b1c) / (jnp.sqrt(v / b2c) + o["eps"])
                                      + o["weight_decay"] * w),
            master, mom, vel)
        return master, grads, mom, vel, gnorm

    def loss_and_grads(self, params, batch, rows: Optional[int] = None):
        tokens, labels = batch["tokens"], batch["labels"]
        n = tokens.shape[0] if rows is None else rows
        total, grads = 0.0, None
        for lo in range(0, n, self.rows):
            hi = min(n, lo + self.rows)
            l, g = self._grad(params, jnp.asarray(tokens[lo:hi]), jnp.asarray(labels[lo:hi]))
            total = total + l
            grads = g if grads is None else self._add(grads, g)
        count = n * tokens.shape[1]
        return total / count, jax.tree.map(lambda g: g / count, grads)

    def run(self, params, batches: List[Dict], rows: Optional[int] = None):
        """(losses, per-leaf norms of step 1's clipped gradient, per-leaf
        norms of the weights' change over all steps)."""
        master = jax.tree.map(lambda a: a.astype(F32), params)
        start = master
        mom = jax.tree.map(jnp.zeros_like, master)
        vel = jax.tree.map(jnp.zeros_like, master)
        losses, grad_norms, self.global_grad_norms = [], None, []
        for k, batch in enumerate(batches, start=1):
            loss, grads = self.loss_and_grads(master, batch, rows)
            master, clipped, mom, vel, gnorm = self._adam(
                master, grads, mom, vel, jnp.float32(k), jnp.float32(lr_at(k, self.o)))
            losses.append(float(loss))
            self.global_grad_norms.append(float(gnorm))
            if k == 1:
                grad_norms = leaf_norms(clipped)
        change = leaf_norms(jax.tree.map(jnp.subtract, master, start))
        return losses, grad_norms, change


def leaf_norms(tree) -> Dict[str, float]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    norms = jax.device_get([jnp.linalg.norm(v.astype(F32).ravel()) for _, v in flat])
    return {jax.tree_util.keystr(path): float(n) for (path, _), n in zip(flat, norms)}


def gaps(got: Tuple, want: Tuple, exclude_below: float = 1e-3) -> Dict[str, float]:
    """The numbers read: each step's relative loss gap and the worst of
    them, the worst and the median leaf's gap of norms of step 1's
    gradient, and the worst leaf's of the change over the steps, each gap
    against the larger of that leaf's reference norm and the median leaf's.
    Leaves whose reference gradient is under ``exclude_below`` of the
    median leaf's are left out of the change."""
    import numpy as np

    (l1, g1, c1), (l0, g0, c0) = got, want
    loss_gaps = [abs(a - b) / abs(b) for a, b in zip(l1, l0)]
    gmed = float(np.median(list(g0.values())))
    grad_gaps = [abs(g1[k] - g0[k]) / max(g0[k], gmed) for k in g0]
    moved = [k for k in c0 if g0[k] >= exclude_below * gmed]
    cmed = float(np.median([c0[k] for k in moved]))
    change_gap = max(abs(c1[k] - c0[k]) / max(c0[k], cmed) for k in moved)
    out = {f"loss_gap_step{k}": g for k, g in enumerate(loss_gaps, start=1)}
    out.update(loss_gap=max(loss_gaps), grad_norm_gap=max(grad_gaps),
               grad_norm_gap_median=float(np.median(grad_gaps)),
               change_norm_gap=change_gap)
    return out


def flops_per_token(m: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token requires
    (``peaks.mamba2_flops_per_token``)."""
    return peaks.mamba2_flops_per_token(m)
