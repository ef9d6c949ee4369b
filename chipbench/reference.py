"""Plain numpy MPI semantics over stacked ``(p, n)`` rank rows, and the
control: the same reference computed in the next narrower type.

The configuration states exact results (``"guarantees": {"exact": true}``):
integer sums wrap in 32 bits whatever the schedule's order, and ``max`` is
exact in any order. So the comparison is bit for bit, and the control, which
computes int32 in int16 and float32 in bfloat16, breaks that guarantee.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np


def _identity(op: str, dtype) -> object:
    if op == "sum":
        return 0
    if np.issubdtype(dtype, np.integer):
        return np.iinfo(dtype).min
    return -np.inf


def collective(coll: str, op: str, x: np.ndarray, root: int = 0) -> np.ndarray:
    """MPI_Scan / MPI_Exscan / MPI_Allreduce / MPI_Reduce over rank rows."""
    p = x.shape[0]
    if coll == "BARRIER":
        return np.ones(p, np.float32)
    if op == "max":
        scan = np.maximum.accumulate(x, axis=0)
    elif op == "sum":
        scan = np.cumsum(x, axis=0, dtype=x.dtype)
    else:
        raise ValueError(f"unsupported operator {op!r}")
    if coll == "SCAN":
        return scan
    if coll == "EXSCAN":
        first = np.full_like(x[:1], _identity(op, x.dtype))
        return np.concatenate([first, scan[:-1]])
    if coll == "ALLREDUCE":
        return np.broadcast_to(scan[-1:], x.shape).copy()
    if coll == "REDUCE":
        out = np.zeros_like(x)
        out[root] = scan[-1]
        return out
    raise ValueError(f"unsupported collective {coll!r}")


#: the control's narrower type for each payload type
NARROWER = {np.dtype(np.int32): np.int16, np.dtype(np.float32): ml_dtypes.bfloat16}


def control(coll: str, op: str, x: np.ndarray, root: int = 0) -> np.ndarray:
    """The reference computed in the next narrower type, widened back."""
    narrow = NARROWER[np.dtype(x.dtype)]
    return collective(coll, op, x.astype(narrow), root).astype(x.dtype)
