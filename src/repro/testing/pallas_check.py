"""Bitwise validation of the fused-Pallas-kernel backend against lower_spmd.

Run:  python -m repro.testing.pallas_check [p]
One shard_map program per case on a 1D mesh of ``p`` host devices: the
plan is lowered once through ``lower_spmd`` (the op-per-round reference)
and once through ``lower_pallas`` (every exchange round of each phase
fused into one interpret-mode Pallas kernel with async-remote-copy sends
and semaphore waits), and the outputs must match bit for bit. Covers
SCAN/EXSCAN over sum, BARRIER, and the hand-fused FUSED_SCAN_TOTAL phase
in both inclusive and exclusive forms. Operators without a zero identity
(max, the SSD pytree operator) are *outside* the kernel's capability —
its ppermute-style zero-fill recv IS the identity handling — so for
those the check asserts ``supports_plan`` rejects the plan with the
stable ``op_flags`` token the engine's fallback telemetry counts.
Prints one ``pallas_check,...`` CSV row per case and a
final ALL-OK; exits nonzero on mismatch. Used by
tests/test_pallas_backend.py and ``scripts/ci.sh`` via subprocess (device
count must be fixed before jax import).
"""

import dataclasses
import os
import sys

_P = int(sys.argv[1]) if len(sys.argv) > 1 else 8
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_P} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402
from repro.core import SSD  # noqa: E402
from repro.core.operators import get_operator  # noqa: E402
from repro.kernels import pallas_collective  # noqa: E402
from repro.offload.planner import (  # noqa: E402
    PhaseKind,
    PlanPhase,
    build_plan,
    lower_spmd,
)


def _fused_plan(p, op, payload_bytes, *, inclusive):
    """A hand-fused single-axis FUSED_SCAN_TOTAL plan (what the pass
    pipeline emits for SCAN+TOTAL pairs; built directly so the check does
    not depend on the optimizer's fusion trigger)."""
    base = build_plan(
        "SCAN" if inclusive else "EXSCAN", (p,), op, payload_bytes
    )
    phase = PlanPhase(
        PhaseKind.FUSED_SCAN_TOTAL,
        0,
        "fused_doubling",
        inclusive=inclusive,
        src=("x",),
        dst="y",
        dst2="t",
    )
    return dataclasses.replace(base, phases=(phase,), result="y")


def _run_pair(mesh, plan, op, x):
    """(reference, pallas) outputs of one plan under shard_map."""
    spec = P("i")

    def wrap(lowered):
        def body(*args):
            out = lowered(args[0] if args else None)
            # rank-0 leaves (the barrier token) need a leading axis for
            # the out_spec; payload leaves already carry the shard axis
            return jax.tree.map(
                lambda a: a[None] if jnp.ndim(a) == 0 else a, out
            )

        return jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(spec,) if x is not None else (),
                out_specs=spec,
                check_vma=False,
            )
        )

    ref_fn = wrap(lower_spmd(plan, ("i",), op))
    got_fn = wrap(
        pallas_collective.lower_pallas(plan, op, axis_names=("i",))
    )
    args = (x,) if x is not None else ()
    return ref_fn(*args), got_fn(*args)


def main() -> None:
    p = _P
    assert len(jax.devices()) == p, (len(jax.devices()), p)
    mesh = Mesh(np.array(jax.devices()), ("i",))
    rng = np.random.default_rng(11)
    failures = 0

    def report(case, ok):
        nonlocal failures
        print(f"pallas_check,{case},p,{p},bitwise,{int(ok)}")
        failures += 0 if ok else 1

    n = 32
    x = jnp.asarray(rng.integers(-4, 5, size=(p, n)).astype(np.float32))
    for coll in ("SCAN", "EXSCAN"):
        op = get_operator("sum")
        plan = build_plan(coll, (p,), op, 4 * n)
        ref, got = _run_pair(mesh, plan, op, x)
        ok = all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got))
        )
        report(f"{coll.lower()}:sum", ok)

    # operators without a zero identity are outside the kernel's
    # capability envelope: the contract is a clean supports_plan
    # rejection (the engine soft-falls back on this token), never a
    # wrong answer or a crash inside the kernel
    for opname, op in (("max", get_operator("max")), ("ssd", SSD)):
        plan = build_plan("SCAN", (p,), op, 4 * n)
        supported, reason = pallas_collective.supports_plan(plan, ("i",))
        ok = (not supported) and reason == "op_flags"
        report(f"scan:{opname}:rejected:{reason or 'none'}", ok)

    # barrier (no payload; output is the fence token)
    op = get_operator("max")
    plan = build_plan("BARRIER", (p,), op, 4)
    ref, got = _run_pair(mesh, plan, op, None)
    ok = all(
        np.array_equal(np.asarray(u), np.asarray(v))
        for u, v in zip(jax.tree.leaves(ref), jax.tree.leaves(got))
    )
    report("barrier", ok)

    # hand-fused SCAN+TOTAL, both forms; lower_spmd returns the plan's
    # result register, so each output is observed by re-pointing `result`
    op = get_operator("sum")
    for inclusive in (True, False):
        for result in ("y", "t"):
            plan = dataclasses.replace(
                _fused_plan(p, op, 4 * n, inclusive=inclusive),
                result=result,
            )
            ref, got = _run_pair(mesh, plan, op, x)
            ok = all(
                np.array_equal(np.asarray(u), np.asarray(v))
                for u, v in zip(
                    jax.tree.leaves(ref), jax.tree.leaves(got)
                )
            )
            form = "inc" if inclusive else "exc"
            out = "scan" if result == "y" else "total"
            report(f"fused_scan_total:{form}:{out}", ok)

    if failures:
        print(f"FAILURES: {failures}")
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
