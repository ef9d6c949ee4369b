"""Host-orchestrated scan — the "software MPI" baseline.

The paper's comparison axis is *who drives the schedule*: software MPI has the
host CPU issue every send/recv (one kernel-launch-equivalent per hop, protocol
stack in the loop), while the offloaded version hands the NIC one descriptor
and receives one result.

The JAX analogue: the *offloaded* path compiles the entire schedule into one
XLA program (``dist_scan`` inside ``shard_map``); the *software* path below
re-enters Python between every schedule step — one jitted step per hop, with a
``block_until_ready`` modelling the host's synchronous involvement, exactly the
dispatch pattern an un-offloaded MPI progress engine exhibits. The benchmark
suite (paper Figs. 4-5) measures both over identical schedules and payloads.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any, Callable, List, Tuple

import jax
import jax.numpy as jnp

from repro.core import algorithms as alg
from repro.core.operators import AssocOp, get_operator

PyTree = Any


class _RecordingBackend(alg.SimBackend):
    """SimBackend that records the permutation of every schedule step."""

    def __init__(self, p: int):
        super().__init__(p)
        self.steps: List[alg.Perm] = []

    def permute(self, tree, perm):
        self.steps.append(list(perm))
        return super().permute(tree, perm)


def schedule_trace(algorithm: str, p: int) -> List[alg.Perm]:
    """Extract the hop list of a schedule (used by benches + latency model)."""
    backend = _RecordingBackend(p)
    op = get_operator("sum")
    x = jnp.zeros((p, 1), dtype=jnp.float32)
    alg.get_algorithm(algorithm)(backend, x, op)
    return backend.steps


def host_scan(
    stacked: PyTree,
    op: "AssocOp | str",
    p: int,
    *,
    algorithm: str,
) -> PyTree:
    """Run the schedule with the host in the loop (one dispatch per step).

    ``stacked`` carries a leading rank axis of size p on a single device —
    logically one buffer per rank, as on the paper's 8 hosts. Each step is an
    independently jitted program; the host synchronizes between steps. The
    result equals ``sim_scan`` / ``dist_scan`` bit-for-bit.
    """
    op = get_operator(op)
    backend = _HostSteppedBackend(p)
    out = alg.get_algorithm(algorithm)(backend, stacked, op)
    return jax.tree.map(lambda a: a.block_until_ready(), out)


class _HostSteppedBackend(alg.SimBackend):
    """Each permute is its own dispatch + host sync (the un-offloaded path)."""

    def permute(self, tree, perm):
        out = _jit_shuffle(self.p, tuple(perm), tree)
        jax.tree.map(lambda a: a.block_until_ready(), out)
        return out


@partial(jax.jit, static_argnums=(0, 1))
def _jit_shuffle(
    p: int, perm: Tuple[Tuple[int, int], ...], tree: PyTree
) -> PyTree:
    return alg.SimBackend(p).permute(tree, list(perm))


def time_host_scan(
    stacked: PyTree, op, p: int, *, algorithm: str, iters: int = 20
) -> float:
    """Median wall-clock seconds per host-orchestrated scan."""
    host_scan(stacked, op, p, algorithm=algorithm)  # warm the per-step jits
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        host_scan(stacked, op, p, algorithm=algorithm)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def time_offloaded_scan(
    stacked: PyTree, op, p: int, *, algorithm: str, iters: int = 20
) -> float:
    """Median wall-clock seconds for the fused (single-program) schedule.

    Same simulator semantics, but the whole schedule is one jitted program —
    one dispatch total, like one offload packet.
    """
    from repro.core.scan_collective import sim_scan

    op = get_operator(op)
    fused = jax.jit(
        lambda s: sim_scan(s, op, p, algorithm=algorithm, inclusive=True)
    )
    out = fused(stacked)
    jax.tree.map(lambda a: a.block_until_ready(), out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fused(stacked)
        jax.tree.map(lambda a: a.block_until_ready(), out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]
