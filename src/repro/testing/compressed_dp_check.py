"""int8+error-feedback gradient sync: convergence parity vs f32 DP.

8-way data-parallel toy regression trained twice — exact f32 psum vs
compressed_allreduce_mean — final losses must both reach tolerance and track
each other. Run: python -m repro.testing.compressed_dp_check
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import sys  # noqa: E402
from functools import partial  # noqa: E402

import jax  # noqa: E402
from jax import shard_map  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from repro.optim.compression import compressed_allreduce_mean  # noqa: E402


def main() -> None:
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(16,)).astype(np.float32)
    X = rng.normal(size=(8, 64, 16)).astype(np.float32)  # per-rank shards
    y = X @ w_true + 0.01 * rng.normal(size=(8, 64)).astype(np.float32)

    def local_grad(w, Xl, yl):
        pred = Xl @ w
        return Xl.T @ (pred - yl) / yl.size

    def make_train(compressed: bool):
        def step(w, err, Xl, yl):
            Xl, yl = Xl[0], yl[0]  # strip the sharded leading rank dim
            g = local_grad(w, Xl, yl)
            if compressed:
                gm, err = compressed_allreduce_mean({"w": g}, "dp", err)
                g = gm["w"]
            else:
                g = jax.lax.pmean(g, "dp")
            return w - 0.1 * g, err

        mapped = shard_map(
            step, mesh=mesh,
            in_specs=(P(), {"w": P()}, P("dp", None, None), P("dp", None)),
            out_specs=(P(), {"w": P()}),
            check_vma=False,
        )
        return jax.jit(mapped)

    losses = {}
    for compressed in (False, True):
        w = jnp.zeros(16)
        err = {"w": jnp.zeros(16)}
        train = make_train(compressed)
        for _ in range(150):
            w, err = train(w, err, jnp.asarray(X), jnp.asarray(y))
        loss = float(np.mean((X.reshape(-1, 16) @ np.asarray(w) - y.reshape(-1)) ** 2))
        losses[compressed] = loss
        print(f"compressed={compressed}: final mse {loss:.5f}")

    ok = losses[True] < 5e-3 and losses[False] < 5e-3
    print("convergence parity:", "OK" if ok else "FAIL")
    print("ALL-OK" if ok else "FAILED")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
