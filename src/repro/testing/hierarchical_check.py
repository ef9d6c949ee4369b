"""SPMD validation of the two-level hierarchical scan on a real 2D mesh.

Run:  python -m repro.testing.hierarchical_check [p_outer p_inner]
Prints one line per case and a final ALL-OK; exits nonzero on mismatch. Used
by tests/test_hierarchical_scan.py via subprocess (device count must be fixed
before jax import).
"""

import os
import sys

_PO = int(sys.argv[1]) if len(sys.argv) > 1 else 2
_PI = int(sys.argv[2]) if len(sys.argv) > 2 else 4
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_PO * _PI} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
from jax import shard_map  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core import SSD  # noqa: E402
from repro.offload import dist_hierarchical_scan  # noqa: E402


def main() -> None:
    po, pi = _PO, _PI
    ptotal = po * pi
    assert len(jax.devices()) == ptotal, (len(jax.devices()), ptotal)
    mesh = Mesh(
        np.array(jax.devices()).reshape(po, pi), ("outer", "inner")
    )
    rng = np.random.default_rng(7)
    failures = 0

    def run(x, op, inclusive):
        def body(xs):
            return dist_hierarchical_scan(
                xs, op, "inner", "outer", inclusive=inclusive
            )

        spec = P(("outer", "inner"))
        m = shard_map(body, mesh=mesh, in_specs=spec, out_specs=spec)
        return np.asarray(jax.jit(m)(x))

    n = 16
    x = rng.integers(-4, 5, size=(ptotal, n)).astype(np.float32)
    for opname, acc in (("sum", np.cumsum), ("max", np.maximum.accumulate)):
        want = acc(x, axis=0)
        got = run(jnp.asarray(x), opname, True)
        ok = np.array_equal(got, want)
        print(f"hier2d scan   {opname:4s} {po}x{pi} {'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1
    # exclusive sum
    want = np.concatenate([np.zeros((1, n), np.float32),
                           np.cumsum(x, axis=0)[:-1]])
    got = run(jnp.asarray(x), "sum", False)
    ok = np.array_equal(got, want)
    print(f"hier2d exscan sum  {po}x{pi} {'OK' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    # non-commutative SSD pytree operator across both axes
    a = rng.uniform(0.5, 1.0, size=(ptotal, 8)).astype(np.float32)
    b = rng.normal(size=(ptotal, 8)).astype(np.float32)
    A = np.empty_like(a)
    B = np.empty_like(b)
    A[0], B[0] = a[0], b[0]
    for j in range(1, ptotal):
        A[j] = a[j] * A[j - 1]
        B[j] = a[j] * B[j - 1] + b[j]

    def body(xs):
        return dist_hierarchical_scan(xs, SSD, "inner", "outer")

    spec = P(("outer", "inner"))
    m = shard_map(
        body, mesh=mesh, in_specs=((spec, spec),), out_specs=(spec, spec)
    )
    ga, gb = jax.jit(m)((jnp.asarray(a), jnp.asarray(b)))
    ok = np.allclose(np.asarray(ga), A, atol=1e-5) and np.allclose(
        np.asarray(gb), B, atol=1e-5
    )
    print(f"hier2d scan   ssd  {po}x{pi} {'OK' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    if failures:
        print(f"FAILURES: {failures}")
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
