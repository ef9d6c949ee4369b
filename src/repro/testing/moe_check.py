"""EP MoE vs dense-dispatch equivalence on a forced 8-device mesh.

With a generous capacity factor (no drops), the expert-parallel sort/
all_to_all path must reproduce the dropless dense reference bit-close.
Run: python -m repro.testing.moe_check
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import dataclasses  # noqa: E402
import sys  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.models.moe import _dense_moe, init_moe, moe_block  # noqa: E402
from repro.sharding.specs import make_topology, use_topology  # noqa: E402


def main() -> None:
    cfg = dataclasses.replace(
        get_config("olmoe_1b_7b").reduced(),
        moe_num_experts=8,
        moe_top_k=2,
        capacity_factor=8.0,  # no drops -> exact match with dense path
    )
    key = jax.random.key(0)
    p = init_moe(key, cfg, jnp.float32)
    rng = np.random.default_rng(0)
    B, S = 4, 16
    x = jnp.asarray(rng.normal(size=(B, S, cfg.d_model)).astype(np.float32))

    want, aux_want = _dense_moe(p, x, cfg, "silu")

    mesh = auto_mesh((2, 4), ("data", "model"))
    topo = make_topology(mesh)
    with use_topology(topo):
        got, aux_got = jax.jit(lambda pp, xx: moe_block(pp, xx, cfg, act="silu"))(p, x)

    ok = np.allclose(np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)
    print("ep-vs-dense outputs:", "OK" if ok else "FAIL",
          float(np.abs(np.asarray(got) - np.asarray(want)).max()))
    ok2 = abs(float(aux_got["load_balance"]) - float(aux_want["load_balance"])) < 1e-3
    print("aux load_balance:", "OK" if ok2 else "FAIL")

    # capacity dropping: tiny capacity factor must not produce NaNs and must
    # reduce output magnitude (dropped tokens get zero expert contribution)
    cfg_drop = dataclasses.replace(cfg, capacity_factor=0.25)
    with use_topology(topo):
        got_d, _ = jax.jit(
            lambda pp, xx: moe_block(pp, xx, cfg_drop, act="silu")
        )(p, x)
    ok3 = np.isfinite(np.asarray(got_d)).all()
    print("capacity-drop finite:", "OK" if ok3 else "FAIL")

    if ok and ok2 and ok3:
        print("ALL-OK")
    else:
        sys.exit(1)


if __name__ == "__main__":
    main()
