"""SPMD validation of the offloaded scan: dist_scan under shard_map.

Run:  python -m repro.testing.spmd_check [ndev]
Prints one line per (algorithm, op, case) and a final ALL-OK. Exits nonzero on
the first mismatch. Used by tests/test_dist_scan.py via subprocess.
"""

import os
import sys

_NDEV = int(sys.argv[1]) if len(sys.argv) > 1 else 8
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_NDEV} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
from jax import shard_map  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core import (  # noqa: E402
    ALGORITHMS,
    SSD,
    dist_exscan,
    dist_scan,
    dist_scan_pair,
    get_operator,
)


def main() -> None:
    p = _NDEV
    assert len(jax.devices()) == p, (len(jax.devices()), p)
    mesh = Mesh(np.array(jax.devices()), ("r",))
    rng = np.random.default_rng(42)
    failures = 0

    def run(fn, x, op, algorithm, inclusive):
        def body(xs):
            f = dist_scan if inclusive else dist_exscan
            return f(xs, op, "r", algorithm=algorithm)

        m = shard_map(body, mesh=mesh, in_specs=P("r"), out_specs=P("r"))
        return np.asarray(jax.jit(m)(x))

    # sum / max over a (p, n) payload sharded one row per rank
    for opname in ("sum", "max"):
        op = get_operator(opname)
        x = rng.normal(size=(p, 64)).astype(np.float32)
        if opname == "sum":
            inc = np.cumsum(x, axis=0)
        else:
            inc = np.maximum.accumulate(x, axis=0)
        for algorithm in ALGORITHMS:
            if algorithm == "invertible_doubling" and (
                op.inverse is None or not op.commutative
            ):
                continue
            got = run(dist_scan, jnp.asarray(x), op, algorithm, True)
            ok = np.allclose(got, inc, atol=1e-4)
            print(f"scan   {opname:4s} {algorithm:22s} {'OK' if ok else 'FAIL'}")
            failures += 0 if ok else 1
            if opname == "sum":
                ex = np.concatenate([np.zeros((1, 64), np.float32), inc[:-1]])
                gex = run(dist_exscan, jnp.asarray(x), op, algorithm, False)
                ok = np.allclose(gex, ex, atol=1e-4)
                print(
                    f"exscan {opname:4s} {algorithm:22s} {'OK' if ok else 'FAIL'}"
                )
                failures += 0 if ok else 1

    # SSD pytree operator (the sequence-parallel Mamba2 state op)
    a = rng.uniform(0.5, 1.0, size=(p, 8)).astype(np.float32)
    b = rng.normal(size=(p, 8)).astype(np.float32)
    A = np.empty_like(a)
    B = np.empty_like(b)
    A[0], B[0] = a[0], b[0]
    for j in range(1, p):
        A[j] = a[j] * A[j - 1]
        B[j] = a[j] * B[j - 1] + b[j]
    for algorithm in ("sequential", "binomial_tree", "recursive_doubling",
                      "sklansky", "hillis_steele", "sequential_pipelined"):
        def body(xs):
            return dist_scan(xs, SSD, "r", algorithm=algorithm)

        m = shard_map(
            body, mesh=mesh, in_specs=((P("r"), P("r")),), out_specs=P("r")
        )
        ga, gb = jax.jit(m)((jnp.asarray(a), jnp.asarray(b)))
        ok = np.allclose(np.asarray(ga), A, atol=1e-4) and np.allclose(
            np.asarray(gb), B, atol=1e-4
        )
        print(f"scan   ssd  {algorithm:22s} {'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1

    # auto-selection end-to-end + scan_pair consistency
    x = rng.normal(size=(p, 32)).astype(np.float32)

    def body(xs):
        return dist_scan_pair(xs, "sum", "r", algorithm="auto")

    m = shard_map(body, mesh=mesh, in_specs=P("r"), out_specs=P("r"))
    ex, inc = jax.jit(m)(jnp.asarray(x))
    winc = np.cumsum(x, axis=0)
    wex = np.concatenate([np.zeros((1, 32), np.float32), winc[:-1]])
    ok = np.allclose(np.asarray(inc), winc, atol=1e-4) and np.allclose(
        np.asarray(ex), wex, atol=1e-4
    )
    print(f"pair   sum  {'auto':22s} {'OK' if ok else 'FAIL'}")
    failures += 0 if ok else 1

    if failures:
        print(f"FAILURES: {failures}")
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
