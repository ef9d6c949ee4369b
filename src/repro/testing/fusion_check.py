"""SPMD validation of the plan-optimizer pass pipeline on a real mesh.

Run:  python -m repro.testing.fusion_check [outer inner]

Every CollType dispatches twice through one ``OffloadEngine`` in **driver
mode** over an (outer, inner) device mesh — once with the descriptor's
``optimized`` flag set (pass pipeline on: SCAN+TOTAL fusion, dead-phase
elimination, permute threading) and once without — and the results must be
bitwise identical to each other and to the flat single-axis reference.
SCAN/EXSCAN additionally run **inside** ``shard_map`` (spmd mode) so the
fused phase's ``lower_spmd`` path is exercised on real named axes, and one
optimized dispatch runs under ``jax.profiler`` so the telemetry gains a
measured-on-device latency source (``device_latency_by_coll_us``), closing
the ROADMAP "SPMD-mode engine telemetry" loop. Prints the optimized plan's
``describe()`` (fused phases + per-plan permute chain), one line per case,
a ``fusion_check_summary`` row for the CI gate, and ALL-OK; exits nonzero
on mismatch. Used by tests/test_passes.py via subprocess (device count
must be fixed before jax import).
"""

import os
import sys

_AXES = (
    (int(sys.argv[1]), int(sys.argv[2])) if len(sys.argv) > 2 else (2, 2)
)
_P = _AXES[0] * _AXES[1]
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={_P} "
    + os.environ.get("XLA_FLAGS", "")
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from jax import shard_map  # noqa: E402
from repro.core import CollType, sim_reduce, sim_scan  # noqa: E402
from repro.offload import (  # noqa: E402
    OffloadEngine,
    build_plan,
    optimize_plan,
    plan_comm_rounds,
)

AXIS_NAMES = ("outer", "inner")


def main() -> None:
    axes = _AXES
    ptotal = _P
    assert len(jax.devices()) == ptotal, (len(jax.devices()), ptotal)
    mesh = Mesh(np.array(jax.devices()).reshape(axes), AXIS_NAMES)
    eng = OffloadEngine()
    rng = np.random.default_rng(11)
    failures = 0
    n = 8
    x = rng.integers(-5, 6, size=(ptotal, n)).astype(np.float32)
    xj = jnp.asarray(x)

    def check(name, ok):
        nonlocal failures
        print(f"fusion {name:34s} {'x'.join(map(str, axes))} "
              f"{'OK' if ok else 'FAIL'}")
        failures += 0 if ok else 1

    def flat_ref(coll, root=0):
        if coll == CollType.SCAN:
            return np.asarray(sim_scan(xj, "sum", ptotal,
                                       algorithm="hillis_steele"))
        if coll == CollType.EXSCAN:
            return np.asarray(sim_scan(xj, "sum", ptotal,
                                       algorithm="hillis_steele",
                                       inclusive=False))
        if coll == CollType.REDUCE:
            return np.asarray(sim_reduce(xj, "sum", ptotal, root=root))
        if coll == CollType.ALLREDUCE:
            return np.broadcast_to(x.sum(axis=0), x.shape).copy()
        return np.ones((ptotal,), np.float32)

    # the optimized plan the descriptors below compile, rendered for the
    # console: fused phases + the per-plan permute chain must be readable
    shown = optimize_plan(
        build_plan("SCAN", axes, "sum", n * 4, order=(0, 1))
    )
    raw_plan = build_plan("SCAN", axes, "sum", n * 4, order=(0, 1))
    print(shown.describe())
    print(f"fusion rounds scan {plan_comm_rounds(raw_plan)} -> "
          f"{plan_comm_rounds(shown)}")
    raw_ex = build_plan("EXSCAN", axes, "sum", n * 4, order=(0, 1))
    opt_ex = optimize_plan(raw_ex)
    print(f"fusion rounds exscan {plan_comm_rounds(raw_ex)} -> "
          f"{plan_comm_rounds(opt_ex)}")

    # driver mode: optimized vs raw vs flat, every CollType
    root = ptotal - 1 if ptotal > 1 else 0
    for coll in CollType:
        d_opt = eng.make_descriptor(
            coll.name, axes=axes, payload_bytes=n * 4, op="sum",
            root=root, split=(0, 1), optimize=True,
        )
        d_raw = eng.make_descriptor(
            coll.name, axes=axes, payload_bytes=n * 4, op="sum",
            root=root, split=(0, 1), optimize=False,
        )
        assert d_opt.optimized and not d_raw.optimized
        arg = None if coll == CollType.BARRIER else xj
        got_opt = np.asarray(
            eng.offload(d_opt, arg, axis_name=AXIS_NAMES, mesh=mesh)
        ).reshape(-1, *x.shape[1:] if coll != CollType.BARRIER else ())
        got_raw = np.asarray(
            eng.offload(d_raw, arg, axis_name=AXIS_NAMES, mesh=mesh)
        ).reshape(got_opt.shape)
        want = flat_ref(coll, root=root).reshape(got_opt.shape)
        check(f"driver {coll.name.lower()} opt==raw",
              np.array_equal(got_opt, got_raw))
        check(f"driver {coll.name.lower()} opt==flat",
              np.array_equal(got_opt, want))

    # spmd mode: the fused phase inside shard_map on real named axes
    spec = P(AXIS_NAMES)
    for coll in (CollType.SCAN, CollType.EXSCAN):
        d_opt = eng.make_descriptor(
            coll.name, axes=axes, payload_bytes=n * 4, op="sum",
            split=(0, 1), optimize=True,
        )

        def body(xs, desc=d_opt):
            return eng.offload(desc, xs, axis_name=AXIS_NAMES)

        got = np.asarray(
            jax.jit(shard_map(body, mesh=mesh, in_specs=spec,
                              out_specs=spec))(xj)
        )
        check(f"spmd {coll.name.lower()} fused==flat",
              np.array_equal(got, flat_ref(coll)))

    # profiler-sourced device telemetry on an optimized driver dispatch
    d_opt = eng.make_descriptor(
        "SCAN", axes=axes, payload_bytes=n * 4, op="sum",
        split=(0, 1), optimize=True,
    )
    timing = eng.profile_offload(d_opt, xj, axis_name=AXIS_NAMES, mesh=mesh)
    snap = eng.telemetry.snapshot()
    dev_us = snap["device_latency_by_coll_us"].get("scan", 0.0)
    print(f"fusion profiled scan device_us={dev_us:.1f} "
          f"source={timing.source} events={timing.events}")
    # the acceptance criterion is *profiler-sourced* latency: a wall-clock
    # fallback means the trace pipeline broke and must fail the check
    check("device latency recorded", dev_us > 0)
    check("latency source is the profiler",
          snap["latency_source_by_coll"].get("scan") == "profiler")
    device_ok = dev_us > 0 and timing.source == "profiler"

    rounds_reduced = int(
        plan_comm_rounds(opt_ex) < plan_comm_rounds(raw_ex)
        and plan_comm_rounds(shown) <= plan_comm_rounds(raw_plan)
    )
    bitwise_ok = int(failures == 0)
    print(
        f"fusion_check_summary,bitwise_equal,{bitwise_ok},"
        f"device_latency,{int(device_ok)},rounds_reduced,{rounds_reduced},"
        f"source,{timing.source}"
    )
    if failures:
        print(f"FAILURES: {failures}")
        sys.exit(1)
    print("ALL-OK")


if __name__ == "__main__":
    main()
