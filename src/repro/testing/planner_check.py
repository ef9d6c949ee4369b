"""SPMD validation of planner-lowered collectives on a real 3D (pod) mesh.

Run:  python -m repro.testing.planner_check [pod outer inner]
All five descriptor CollTypes dispatch through ``OffloadEngine`` as *planned*
multi-axis descriptors inside ``shard_map`` over a (pod, outer, inner) device
mesh, and every result is checked against the flat single-axis reference.
One case uses a non-identity split to validate the logical-order layout
contract (the split decides which physical axis varies fastest in global
rank order). Prints one line per case and a final ALL-OK; exits nonzero on
mismatch. Used by tests/test_planner.py via subprocess (device count must be
fixed before jax import).
"""

import os
import sys

_AXES = (
    (int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]))
    if len(sys.argv) > 3
    else (2, 2, 2)
)
_P = _AXES[0] * _AXES[1] * _AXES[2]
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_P} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402
from repro.core import SSD, sim_barrier, sim_reduce, sim_scan  # noqa: E402
from repro.offload import (  # noqa: E402
    OffloadEngine,
    build_plan,
    optimize_plan,
    plan_layout,
)
from repro.sharding.specs import plan_spec  # noqa: E402

AXIS_NAMES = ("pod", "outer", "inner")


def main() -> None:
    axes = _AXES
    ptotal = _P
    assert len(jax.devices()) == ptotal, (len(jax.devices()), ptotal)
    mesh = Mesh(np.array(jax.devices()).reshape(axes), AXIS_NAMES)
    eng = OffloadEngine()
    rng = np.random.default_rng(7)
    failures = 0
    n = 8
    spec = P(AXIS_NAMES)

    def run(desc, x, out_spec=None, in_spec=None):
        def body(xs):
            return eng.offload(desc, xs, axis_name=AXIS_NAMES)

        m = shard_map(
            body,
            mesh=mesh,
            in_specs=in_spec if in_spec is not None else spec,
            out_specs=out_spec if out_spec is not None else spec,
        )
        return jax.jit(m)(x)

    def check(name, ok):
        nonlocal failures
        print(f"planned3d {name:28s} {'x'.join(map(str, axes))} "
              f"{'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1

    x = rng.integers(-4, 5, size=(ptotal, n)).astype(np.float32)
    xj = jnp.asarray(x)

    # the plan trace, raw and optimized — describe() must stay readable
    # after the pass pipeline rewrites the phase list (fused phases render
    # with both outputs, the permute chain renders once per plan)
    plan = build_plan("SCAN", axes, "sum", n * 4, order=(0, 1, 2))
    print(plan.describe())
    print(optimize_plan(plan).describe())

    # SCAN / EXSCAN (identity split): bitwise vs the flat reference
    for coll, inclusive in (("SCAN", True), ("EXSCAN", False)):
        desc = eng.make_descriptor(
            coll, axes=axes, payload_bytes=n * 4, op="sum", split=(0, 1, 2)
        )
        got = np.asarray(run(desc, xj))
        want = np.asarray(
            sim_scan(xj, "sum", ptotal, algorithm="hillis_steele",
                     inclusive=inclusive)
        )
        check(f"{coll.lower()} sum", np.array_equal(got, want))

    # SCAN with a non-identity split: innermost logical level on the pod
    # axis. plan_spec shards the logical-rank-ordered payload straight onto
    # the physical mesh (no hand layout), so the result compares directly
    # against the flat logical reference.
    order = (1, 2, 0)
    desc = eng.make_descriptor(
        "SCAN", axes=axes, payload_bytes=n * 4, op="sum", split=order
    )
    layout = plan_layout(desc)
    lspec = plan_spec(layout, AXIS_NAMES, ndim=2)
    got = np.asarray(run(desc, xj, in_spec=lspec, out_spec=lspec))
    want = np.asarray(
        sim_scan(xj, "sum", ptotal, algorithm="hillis_steele")
    )
    check(f"scan sum split={order}", np.array_equal(got, want))
    # the layout's flat permutations agree with the spec-level placement
    rt = layout.to_logical(layout.to_physical(x))
    check("plan_layout round-trip", np.array_equal(np.asarray(rt), x))

    # REDUCE with the root off rank 0
    root = ptotal - 3
    desc = eng.make_descriptor(
        "REDUCE", axes=axes, payload_bytes=n * 4, op="sum", root=root,
        split=(0, 1, 2),
    )
    got = np.asarray(run(desc, xj))
    want = np.asarray(sim_reduce(xj, "sum", ptotal, root=root))
    check(f"reduce sum root={root}", np.array_equal(got, want))

    # ALLREDUCE max
    desc = eng.make_descriptor(
        "ALLREDUCE", axes=axes, payload_bytes=n * 4, op="max", split=(0, 1, 2)
    )
    got = np.asarray(run(desc, xj))
    want = np.broadcast_to(x.max(axis=0), x.shape)
    check("allreduce max", np.array_equal(got, want))

    # BARRIER: token of ones on every rank
    desc = eng.make_descriptor(
        "BARRIER", axes=axes, payload_bytes=4, op="sum", split=(0, 1, 2)
    )

    def barrier_body(xs):
        # per-rank scalar token -> singleton axis so shards concatenate
        return eng.offload(desc, xs, axis_name=AXIS_NAMES).reshape(1)

    m = shard_map(barrier_body, mesh=mesh, in_specs=spec, out_specs=spec)
    got = np.asarray(jax.jit(m)(jnp.zeros((ptotal,), jnp.float32)))
    want = np.asarray(sim_barrier(ptotal))
    check("barrier", np.array_equal(got, want))

    # non-commutative SSD pytree operator across all three axes
    a = rng.uniform(0.5, 1.0, size=(ptotal, n)).astype(np.float32)
    b = rng.normal(size=(ptotal, n)).astype(np.float32)
    A, B = np.empty_like(a), np.empty_like(b)
    A[0], B[0] = a[0], b[0]
    for j in range(1, ptotal):
        A[j] = a[j] * A[j - 1]
        B[j] = a[j] * B[j - 1] + b[j]
    desc = eng.make_descriptor(
        "SCAN", axes=axes, payload_bytes=2 * n * 4, op="ssd", split=(0, 1, 2)
    )
    ga, gb = run(
        desc,
        (jnp.asarray(a), jnp.asarray(b)),
        in_spec=((spec, spec),),
        out_spec=(spec, spec),
    )
    ok = np.allclose(np.asarray(ga), A, atol=1e-5) and np.allclose(
        np.asarray(gb), B, atol=1e-5
    )
    check("scan ssd", ok)

    # repeat dispatch of an identical descriptor must hit the plan cache
    hits_before = eng.telemetry.hits
    desc = eng.make_descriptor(
        "SCAN", axes=axes, payload_bytes=n * 4, op="sum", split=(0, 1, 2)
    )
    _ = run(desc, xj)
    check("plan cache hit", eng.telemetry.hits > hits_before)

    if failures:
        print(f"FAILURES: {failures}")
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
