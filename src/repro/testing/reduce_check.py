"""SPMD validation of dist_reduce / dist_allreduce / dist_barrier (8 devices).

Run: python -m repro.testing.reduce_check
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import sys  # noqa: E402

import jax  # noqa: E402
from jax import shard_map  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.core import dist_allreduce, dist_barrier, dist_reduce  # noqa: E402


def main() -> None:
    p = 8
    mesh = Mesh(np.array(jax.devices()), ("r",))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(p, 32)).astype(np.float32)
    failures = 0

    # reduce to root=2: only root holds the sum, others identity (0)
    def red(xs):
        return dist_reduce(xs, "sum", "r", root=2)

    got = np.asarray(
        jax.jit(shard_map(red, mesh=mesh, in_specs=P("r"), out_specs=P("r")))(
            jnp.asarray(x)
        )
    )
    want_total = x.sum(0)
    ok = np.allclose(got[2], want_total, atol=1e-4) and np.allclose(
        np.delete(got, 2, axis=0), 0.0
    )
    print("reduce(root=2):", "OK" if ok else "FAIL")
    failures += 0 if ok else 1

    # allreduce: every rank has the total; matches lax.psum
    def ar(xs):
        return dist_allreduce(xs, "sum", "r")

    got = np.asarray(
        jax.jit(shard_map(ar, mesh=mesh, in_specs=P("r"), out_specs=P("r")))(
            jnp.asarray(x)
        )
    )
    ok = all(np.allclose(got[i], want_total, atol=1e-4) for i in range(p))
    print("allreduce:", "OK" if ok else "FAIL")
    failures += 0 if ok else 1

    # max-allreduce (non-zero identity path)
    def arm(xs):
        return dist_allreduce(xs, "max", "r")

    got = np.asarray(
        jax.jit(shard_map(arm, mesh=mesh, in_specs=P("r"), out_specs=P("r")))(
            jnp.asarray(x)
        )
    )
    ok = all(np.allclose(got[i], x.max(0)) for i in range(p))
    print("allreduce(max):", "OK" if ok else "FAIL")
    failures += 0 if ok else 1

    # barrier: compiles, returns 1.0 everywhere
    def bar(xs):
        t = dist_barrier("r")
        return xs * t

    got = np.asarray(
        jax.jit(shard_map(bar, mesh=mesh, in_specs=P("r"), out_specs=P("r")))(
            jnp.asarray(x)
        )
    )
    ok = np.allclose(got, x)
    print("barrier:", "OK" if ok else "FAIL")
    failures += 0 if ok else 1

    if failures:
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
