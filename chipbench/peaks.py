"""Peaks of the chips the benchmark runs on, and the work functions.

Peaks: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
A device kind that is not in the table is an error, never a default.

Work functions count what an algorithm *requires*, from shapes alone:
recomputation, padding and re-reads are the implementation's cost, not work.
"""

from __future__ import annotations

from typing import Any, Dict

PEAKS: Dict[str, Dict[str, Any]] = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def for_device(kind: str) -> Dict[str, Any]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"device kind {kind!r} has no entry in the peaks table "
            f"(known: {sorted(PEAKS)})"
        ) from None


def collective_least_bytes(p: int, bytes_per_rank: int) -> int:
    """Least HBM traffic of a stacked collective over ``p`` ranks: every
    rank's input read once and every rank's output written once, whatever
    schedule implements it."""
    return 2 * p * bytes_per_rank


def mamba2_param_count(c: Dict[str, Any]) -> int:
    """Parameters of the Mamba2 LM the configuration describes (tied head)."""
    d, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    di = c["expand"] * d
    N, P, K = c["d_state"], c["headdim"], c["d_conv"]
    H = di // P
    conv_ch = di + 2 * N
    layer = (
        d * (2 * di + 2 * N + H)   # in-projections z, x, B|C, dt
        + di * d                    # out-projection
        + K * conv_ch + conv_ch     # depthwise conv weights + biases
        + 3 * H                     # A_log, D, dt_bias
        + di                        # gated-norm scale
        + d                         # block norm
    )
    return L * layer + V * d + d    # + tied embedding, final norm


def mamba2_flops_per_token(c: Dict[str, Any]) -> float:
    """Forward + backward FLOPs one token requires (no recompute).

    Matmuls: 2 FLOPs per weight of every projection and of the tied LM head
    (published vocabulary, not the padded one), forward; backward twice
    that. The depthwise conv counts 2 per weight. SSD (chunked dual form,
    chunk Q, one B/C group): per token and layer, scores C.B^T over the
    chunk (2*Q*N), the chunk-local output (2*Q*H*P), the chunk state
    (2*H*P*N) and the state's output (2*H*P*N)."""
    d, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    di = c["expand"] * d
    N, P, K, Q = c["d_state"], c["headdim"], c["d_conv"], c["chunk_size"]
    H = di // P
    mm = L * (d * (2 * di + 2 * N + H) + di * d + K * (di + 2 * N)) + V * d
    ssd = L * (2 * Q * N + 2 * Q * H * P + 4 * H * P * N)
    return 3.0 * (2.0 * mm + ssd)
