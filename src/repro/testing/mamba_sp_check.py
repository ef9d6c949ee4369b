"""Sequence-parallel Mamba2 (dist_exscan across shards) vs single-device.

The SP path shards the sequence over an 8-way model axis; its output and
final SSD state must match the unsharded mixer. This is THE paper-technique
correctness gate: inter-chunk state crosses devices through the offloaded
scan collective, and the conv halo crosses through a neighbor ppermute.
Run: python -m repro.testing.mamba_sp_check
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.models.mamba import init_mamba, mamba_mixer  # noqa: E402
from repro.sharding.specs import make_topology, use_topology  # noqa: E402


def main() -> None:
    cfg = get_config("mamba2_130m").reduced()
    key = jax.random.key(0)
    p = init_mamba(key, cfg, jnp.float32)
    rng = np.random.default_rng(0)
    B, S = 2, 128  # 8 shards x 16 tokens, chunk=16
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)).astype(np.float32) * 0.1)

    y_ref, cache_ref = mamba_mixer(p, x, cfg, seq_parallel=False)

    mesh = auto_mesh((1, 8), ("data", "model"))
    topo = make_topology(mesh)
    with use_topology(topo):
        y_sp, cache_sp = jax.jit(
            lambda pp, xx: mamba_mixer(pp, xx, cfg, seq_parallel=True)
        )(p, x)

    ok = np.allclose(np.asarray(y_ref), np.asarray(y_sp), atol=2e-3, rtol=2e-3)
    print("seq-parallel output:", "OK" if ok else "FAIL",
          float(np.abs(np.asarray(y_ref) - np.asarray(y_sp)).max()))
    ok2 = np.allclose(
        np.asarray(cache_ref["ssm"]), np.asarray(cache_sp["ssm"]),
        atol=2e-3, rtol=2e-3,
    )
    print("final SSD state:", "OK" if ok2 else "FAIL")
    ok3 = np.allclose(
        np.asarray(cache_ref["conv_x"]), np.asarray(cache_sp["conv_x"]),
        atol=1e-4,
    )
    print("conv tail:", "OK" if ok3 else "FAIL")

    # gradient flows through the collective
    def loss(pp):
        with use_topology(topo):
            y, _ = mamba_mixer(pp, x, cfg, seq_parallel=True)
        return jnp.sum(y * y)

    with use_topology(topo):
        g = jax.grad(loss)(p)
    gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
    ok4 = np.isfinite(gn) and gn > 0
    print("grad through dist_exscan:", "OK" if ok4 else "FAIL", gn)

    if ok and ok2 and ok3 and ok4:
        print("ALL-OK")
    else:
        sys.exit(1)


if __name__ == "__main__":
    main()
