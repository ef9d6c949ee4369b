"""Public API of the offloaded scan collective: dist_scan / dist_exscan.

Call these *inside* an SPMD context (``shard_map``) over one mesh axis. The
entire schedule — every hop and every combine — lowers into the compiled XLA
program as collective-permutes, which is the TPU analogue of the paper's
one-descriptor-in, one-result-out NIC offload: the host dispatches a single
program; the network does the rest.

Exclusive scans come in two flavors, mirroring the paper:
  * structural: run the inclusive schedule on shifted inputs (one extra
    single-hop permute) — works for any operator;
  * inverse-op (``algo_type="invertible_doubling"`` or ``use_inverse=True``):
    recover exclusive from inclusive locally via the operator inverse — the
    Fig. 3 subtraction trick, zero extra communication.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import algorithms as alg
from repro.core.operators import AssocOp, get_operator
from repro.core.packet import CollectiveDescriptor
from repro.core.selector import select_algorithm

PyTree = Any


def dist_scan(
    x: PyTree,
    op: "AssocOp | str",
    axis_name: str,
    *,
    algorithm: str = "auto",
    descriptor: Optional[CollectiveDescriptor] = None,
) -> PyTree:
    """Inclusive parallel prefix scan (MPI_Scan) across ``axis_name``.

    Args:
      x: per-rank pytree contribution (leaves may be any shape).
      op: an :class:`AssocOp` or registered name ("sum", "max", "ssd", ...).
      axis_name: mesh axis to scan over (must be an active SPMD axis).
      algorithm: one of ``core.algorithms.ALGORITHMS`` or "auto" to let the
        selector pick from (p, payload bytes) — the paper's runtime-side
        ``algo_type`` choice.
      descriptor: optional offload descriptor; when given, its ``algo_type``
        wins (the software layer pre-assigned roles, as in the paper).
    """
    op = get_operator(op)
    p = lax.axis_size(axis_name)
    if descriptor is not None:
        algorithm = descriptor.algo_type
    if algorithm == "auto":
        algorithm = select_algorithm(p, _payload_bytes(x), op)
    backend = alg.SpmdBackend(axis_name, p)
    return alg.get_algorithm(algorithm)(backend, x, op)


def dist_exscan(
    x: PyTree,
    op: "AssocOp | str",
    axis_name: str,
    *,
    algorithm: str = "auto",
    use_inverse: Optional[bool] = None,
    descriptor: Optional[CollectiveDescriptor] = None,
) -> PyTree:
    """Exclusive scan (MPI_Exscan): rank j gets x_0 (+) ... (+) x_{j-1}.

    Rank 0 receives the operator identity (MPI leaves it undefined; a defined
    identity is strictly more useful and is what our SSM/MoE layers need).
    """
    op = get_operator(op)
    p = lax.axis_size(axis_name)
    if descriptor is not None:
        algorithm = descriptor.algo_type
    if algorithm == "auto":
        algorithm = select_algorithm(p, _payload_bytes(x), op, coll="exscan")
    if use_inverse is None:
        use_inverse = algorithm == "invertible_doubling" and op.inverse is not None

    backend = alg.SpmdBackend(axis_name, p)
    identity = op.identity_like(x)
    if p == 1:
        return identity

    if use_inverse:
        if op.inverse is None:
            raise ValueError(f"op {op.name!r} has no inverse")
        inc = alg.get_algorithm(algorithm)(backend, x, op)
        # y_ex = inv(x) (+) y_inc  — valid because y_inc = x?  No: careful.
        # y_inc = y_ex (+) x  =>  for commutative ops y_ex = y_inc (+) inv(x);
        # for non-commutative ops we need a right-inverse form, so restrict.
        if not op.commutative:
            raise ValueError(
                "inverse-based exscan requires a commutative operator; "
                f"{op.name!r} is not"
            )
        ex = op.combine(inc, op.inverse(x))
        rank = backend.rank()
        return alg._bwhere(rank == 0, identity, ex)

    # Structural: shift contributions one rank to the right, then inclusive
    # scan; rank 0 holds the identity. One extra single-hop permute.
    shifted = backend.permute(x, [(i, i + 1) for i in range(p - 1)])
    rank = backend.rank()
    flag = jnp.where(rank == 0, 0.0, 1.0).astype(jnp.float32)
    if op.zero_identity:
        # zeros already are the identity; plain inclusive scan works.
        return alg.get_algorithm(algorithm)(backend, shifted, op)
    # For non-zero identities, rank 0's "contribution" must read as identity.
    shifted = alg._bwhere(flag > 0.5, shifted, identity)
    return alg.get_algorithm(algorithm)(backend, shifted, op)


def dist_scan_pair(
    x: PyTree,
    op: "AssocOp | str",
    axis_name: str,
    *,
    algorithm: str = "auto",
) -> tuple[PyTree, PyTree]:
    """Return (exclusive, inclusive) in one schedule run.

    The SSM sequence-parallel layer needs the exclusive scan (incoming state)
    but validating against the inclusive value is free: inc = ex (+) x.
    """
    op = get_operator(op)
    ex = dist_exscan(x, op, axis_name, algorithm=algorithm)
    return ex, op.combine(ex, x)


def _payload_bytes(x: PyTree) -> int:
    return sum(
        int(jnp.size(leaf)) * jnp.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(x)
    )


# ---------------------------------------------------------------------------
# Simulator entry point (single device, stacked leading rank axis) — used by
# tests and the software-baseline benchmarks.
# ---------------------------------------------------------------------------


def sim_scan(
    stacked: PyTree,
    op: "AssocOp | str",
    p: int,
    *,
    algorithm: str,
    inclusive: bool = True,
    backend: "alg.Backend | None" = None,
) -> PyTree:
    """Run a schedule on stacked (p, ...) arrays without any mesh.

    ``backend`` overrides the default :class:`~repro.core.algorithms.
    SimBackend` — used by the traced plan interpreter to inject a
    round-counting wrapper; it must behave like a SimBackend of size ``p``.
    """
    op = get_operator(op)
    if backend is None:
        backend = alg.SimBackend(p)
    if inclusive:
        return alg.get_algorithm(algorithm)(backend, stacked, op)
    identity = op.identity_like(stacked)
    if p == 1:
        return identity
    rank = backend.rank()
    if (
        algorithm == "invertible_doubling"
        and op.inverse is not None
        and op.commutative
    ):
        # The Fig. 3 subtraction trick, mirrored from dist_exscan: recover the
        # exclusive value locally, skipping the structural shift permute.
        inc = alg.get_algorithm(algorithm)(backend, stacked, op)
        ex = op.combine(inc, op.inverse(stacked))
        return alg._bwhere(rank != 0, ex, identity)
    shifted = backend.permute(stacked, [(i, i + 1) for i in range(p - 1)])
    if not op.zero_identity:
        shifted = alg._bwhere(rank != 0, shifted, identity)
    out = alg.get_algorithm(algorithm)(backend, shifted, op)
    return alg._bwhere(rank != 0, out, identity)
