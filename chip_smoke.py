#!/usr/bin/env python3
"""Smoke run of the main path on a TPU: kernels, offload engine, broker and
a full-width Mamba2-130M training step, each checked against a reference.

    python chip_smoke.py               # one chip
    python chip_smoke.py --chips 4     # the four-chip mesh phase only

One chip runs four phases:

* ``kernels`` — the Pallas prefix-scan kernel and its custom-VJP backward
  pass, and the SSD-scan kernel, at Mamba2-130M's shapes, against numpy;
* ``engine`` — SCAN, EXSCAN, REDUCE, ALLREDUCE and BARRIER through
  ``OffloadEngine.offload`` over p=8 ranks stacked on the device, at 4 B,
  1 KiB, 1 MiB and 64 MiB per rank, plus one planned ``(2, 2, 2)``
  descriptor and one profiled dispatch; the fused Pallas backend is asked
  for wherever ``supports_plan`` admits the plan, and the engine must not
  fall back;
* ``broker`` — ``build_offload_service()`` serving 4 in-process clients x 16
  requests of mixed collectives and sizes;
* ``trainer`` — ``repro.launch.train``'s Trainer running mamba2-130m at its
  published widths for 3 steps; the compiled step must hold the Pallas
  kernels (``tpu_custom_call``).

``--chips 4`` runs the collectives in driver mode through the engine, as a
flat descriptor over a 4-chip axis and as a planned descriptor over a 2x2
mesh, against the one-device sim result; the broker in driver mode; and the
engine-routed Mamba2-130M data-parallel step against its raw-``lax`` twin,
bit for bit. The fused Pallas backend's spmd form is refused on a chip
(``supports_plan`` reason ``spmd_unverified``), so no pallas leg runs
here.

Every line but the last reports a phase as JSON. The last line, printed only
when every phase passed, is ``{"ok": true, "device": {...}}``. Without a TPU
the script exits non-zero before any work. Data comes from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import threading
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
#: run-time files (checkpoints, the broker's tuning registry); in .gitignore
SCRATCH = ROOT / ".smoke"

MIB = 2**20
SIZES = (4, 1024, MIB, 64 * MIB)
TRAIN_BATCH, TRAIN_SEQ = 4, 2048


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ----------------------------------------------------------------- references


def reference(coll: str, op: str, x, p: int, root: int = 0):
    """numpy MPI semantics over stacked ``(p, n)`` rank rows."""
    if coll == "BARRIER":
        return np.ones(p, np.float32)
    if op == "max":
        scan = np.maximum.accumulate(x, axis=0)
    else:
        scan = np.cumsum(x, axis=0, dtype=x.dtype)
    if coll == "SCAN":
        return scan
    if coll == "EXSCAN":
        return np.concatenate([np.zeros_like(x[:1]), scan[:-1]])
    if coll == "ALLREDUCE":
        return np.broadcast_to(scan[-1:], x.shape)
    out = np.zeros_like(x)  # REDUCE
    out[root] = scan[-1]
    return out


def payloads(rng, p: int, nbytes: int):
    """Integer-valued int32 rows for ``sum`` (every association order gives
    the same bits) and float32 rows for ``max``."""
    n = max(1, nbytes // 4)
    ints = rng.integers(-8, 9, size=(p, n), dtype=np.int32)
    floats = rng.standard_normal((p, n), dtype=np.float32)
    return ints, floats


CASES = (
    ("SCAN", "sum"),
    ("SCAN", "max"),
    ("EXSCAN", "sum"),
    ("REDUCE", "sum"),
    ("ALLREDUCE", "sum"),
    ("BARRIER", "max"),
)


def _descriptor(eng, coll, op, x, **kw):
    from repro.core.packet import WireDType

    if coll == "BARRIER":
        return eng.make_descriptor(coll, payload_bytes=4, op=op, **kw)
    dtype = WireDType.INT32 if x.dtype == np.int32 else WireDType.FLOAT32
    root = x.shape[0] - 1 if coll == "REDUCE" else 0
    return eng.make_descriptor(
        coll, payload_bytes=x[0].nbytes, op=op, data_type=dtype, root=root,
        **kw,
    )


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    out = np.asarray(out)
    return out, time.perf_counter() - t0


# --------------------------------------------------------------- one chip


def phase_kernels(rng) -> bool:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops

    checks = {}
    # Mamba2-130M at batch 4 x 2048: (batch, chunks, heads, chunk) decays
    x = rng.standard_normal((4, 8, 24, 256), dtype=np.float32)
    w = rng.standard_normal(x.shape, dtype=np.float32)
    scan = np.asarray(ops.prefix_scan(x))
    checks["scan"] = np.allclose(scan, np.cumsum(x, -1), rtol=1e-5, atol=1e-4)
    grad = np.asarray(
        jax.grad(lambda v: jnp.sum(w * ops.prefix_scan(v)))(jnp.asarray(x))
    )
    want = np.flip(np.cumsum(np.flip(w, -1), -1), -1)
    checks["scan_grad"] = np.allclose(grad, want, rtol=1e-5, atol=1e-4)
    # native kernels, never interpret mode
    checks["scan_native"] = "tpu_custom_call" in jax.jit(
        ops.prefix_scan
    ).lower(x).compile().as_text()

    # SSD recurrence over (batch, time, d_inner-slice)
    a = rng.uniform(0.9, 1.0, (4, 2048, 256)).astype(np.float32)
    b = rng.standard_normal(a.shape, dtype=np.float32)
    h_want = np.empty_like(b)
    h = np.zeros_like(b[:, 0])
    for t in range(a.shape[1]):
        h = a[:, t] * h + b[:, t]
        h_want[:, t] = h
    h_got, h_last = (np.asarray(v) for v in ops.ssd_scan(a, b))
    scale = np.abs(h_want).max()
    ssd_err = float(np.abs(h_got - h_want).max() / scale)
    checks["ssd"] = ssd_err < 1e-5 and np.array_equal(h_last, h_got[:, -1])
    checks["ssd_native"] = "tpu_custom_call" in jax.jit(
        ops.ssd_scan
    ).lower(a, b).compile().as_text()
    checks = {k: bool(v) for k, v in checks.items()}
    ok = all(checks.values())
    log("kernels", ok=ok, ssd_rel_err=ssd_err, **checks)
    return ok


def phase_engine(rng, p: int = 8, sizes=SIZES) -> bool:
    import jax

    from repro.kernels import pallas_collective
    from repro.launch.offload_runtime import build_offload_engine
    from repro.offload.planner import build_plan

    eng = build_offload_engine(retune_on_remesh=False)
    all_ok = True
    for nbytes in sizes:
        ints, floats = payloads(rng, p, nbytes)
        for coll, op in CASES:
            x = None if coll == "BARRIER" else (floats if op == "max" else ints)
            want = reference(coll, op, x, p, root=p - 1)
            xd = None if x is None else jax.device_put(x)
            desc = _descriptor(eng, coll, op, x, p=p)
            got, first_s = _timed(lambda: eng.offload(desc, xd))
            got, steady_s = _timed(lambda: eng.offload(desc, xd))
            ok = np.array_equal(got, want)
            row = dict(
                coll=coll, op=op, bytes_per_rank=nbytes, sim_ok=bool(ok),
                first_s=first_s, steady_s=steady_s,
            )
            # the plan the engine builds for a planned (1, p) descriptor
            # with every schedule option pinned
            plan = build_plan(
                coll, (1, p), op, 4 if x is None else x[0].nbytes,
                order=(0, 1), root=p - 1 if coll == "REDUCE" else 0,
            )
            admitted, reason = pallas_collective.supports_plan(plan)
            if admitted:
                fallbacks = eng.telemetry.backend_fallbacks
                pdesc = _descriptor(
                    eng, coll, op, x, axes=(1, p), split=(0, 1),
                    optimize=False, chunks=1, backend="pallas",
                )
                pgot, pfirst_s = _timed(lambda: eng.offload(pdesc, xd))
                pgot, psteady_s = _timed(lambda: eng.offload(pdesc, xd))
                pok = (
                    np.array_equal(pgot, want)
                    and eng.telemetry.backend_fallbacks == fallbacks
                )
                ok &= pok
                row.update(
                    pallas_ok=bool(pok), pallas_first_s=pfirst_s,
                    pallas_steady_s=psteady_s,
                )
            else:
                row.update(pallas="refused", reason=reason)
            log("engine", ok=bool(ok), **row)
            all_ok &= bool(ok)

    # one planned three-level descriptor, and one profiled dispatch
    ints, _ = payloads(rng, p, MIB)
    desc = _descriptor(eng, "SCAN", "sum", ints, axes=(2, 2, 2))
    got, first_s = _timed(lambda: eng.offload(desc, jax.device_put(ints)))
    ok = np.array_equal(got, reference("SCAN", "sum", ints, p))
    log("engine", ok=bool(ok), coll="SCAN", axes=[2, 2, 2],
        bytes_per_rank=MIB, first_s=first_s)
    all_ok &= bool(ok)

    timing = eng.profile_offload(
        _descriptor(eng, "SCAN", "sum", ints, p=p), jax.device_put(ints)
    )
    ok = timing.source == "profiler"
    log("engine", ok=bool(ok), profiled="SCAN", bytes_per_rank=MIB,
        device_us=timing.device_us, wall_us=timing.wall_us,
        source=timing.source, events=timing.events,
        fallback_reason=timing.fallback_reason)
    all_ok &= bool(ok)

    t = eng.telemetry.snapshot()
    log("engine", ok=bool(all_ok and t["backend_fallbacks"] == 0),
        compiles=t["compiles"], hits=t["hits"], misses=t["misses"],
        backend_fallbacks=t["backend_fallbacks"])
    return bool(all_ok and t["backend_fallbacks"] == 0)


def _serve(svc, rng, p: int, clients: int = 4, requests: int = 16) -> dict:
    """``clients`` threads x ``requests`` mixed tickets; returns counts."""
    eng = svc.engine
    sizes = (4, 1024, 65536)
    work = []
    for c in range(clients):
        mine = []
        for r in range(requests):
            coll, op = CASES[(c + r) % len(CASES)]
            ints, floats = payloads(rng, p, sizes[r % len(sizes)])
            x = None if coll == "BARRIER" else (floats if op == "max" else ints)
            desc = _descriptor(eng, coll, op, x, p=p)
            mine.append((desc, x, reference(coll, op, x, p, root=p - 1)))
        work.append(mine)
    good, errors = [0] * clients, []

    def run(c: int) -> None:
        try:
            client = svc.client(f"tenant{c}")
            tickets = [client.submit(d.encode(), x) for d, x, _ in work[c]]
            for t, (_, _, want) in zip(tickets, work[c]):
                good[c] += bool(np.array_equal(np.asarray(t.result(300)), want))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=run, args=(c,)) for c in range(clients)]
    t0 = time.perf_counter()
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    wall_s = time.perf_counter() - t0
    svc.stop()
    snap = svc.telemetry.snapshot()
    return dict(
        answered=sum(good), asked=clients * requests, errors=errors[:3],
        wall_s=wall_s, coalesce_factor=snap["coalesce_factor"],
        dispatches=snap["fused_dispatches"],
    )


def phase_broker(rng) -> bool:
    from repro.launch.offload_runtime import build_offload_service

    svc = build_offload_service(
        registry=SCRATCH / "registry", retune_on_remesh=False
    )
    stats = _serve(svc, rng, p=8)
    ok = stats["answered"] == stats["asked"] and not stats["errors"]
    log("broker", ok=bool(ok), mode="sim", **stats)
    return bool(ok)


def phase_trainer(dev) -> bool:
    from repro.launch import train

    ckpt = SCRATCH / "ckpt"
    shutil.rmtree(ckpt, ignore_errors=True)
    args = train.parse_args([
        "--arch", "mamba2-130m", "--full", "--mesh", "local",
        "--steps", "3", "--batch", str(TRAIN_BATCH),
        "--seq", str(TRAIN_SEQ), "--ckpt-dir", str(ckpt),
    ])
    tr = train.build_trainer(args)
    params, opt = tr.init_state()
    t0 = time.perf_counter()
    hlo = tr.step_fn.lower(params, opt, next(tr.data_iter)).compile().as_text()
    compile_s = time.perf_counter() - t0
    kernels = hlo.count("tpu_custom_call")
    t0 = time.perf_counter()
    params, opt, hist = tr.run(params, opt, 3)
    run_s = time.perf_counter() - t0
    shutil.rmtree(ckpt, ignore_errors=True)
    losses = [h["loss"] for h in hist]
    ok = kernels > 0 and len(losses) == 3 and bool(np.isfinite(losses).all())
    log("trainer", ok=bool(ok), arch="mamba2-130m", widths="published",
        batch=TRAIN_BATCH, seq=TRAIN_SEQ, cut="none", compile_s=compile_s,
        run_s=run_s, step_s=[h["step_time_s"] for h in hist], loss=losses,
        tpu_custom_calls=kernels,
        peak_bytes_in_use=_peak_bytes(dev))
    return bool(ok)


def _peak_bytes(dev):
    return (dev.memory_stats() or {}).get("peak_bytes_in_use")


# -------------------------------------------------------------- four chips


def phase_mesh_collectives(rng, devs, sizes=SIZES) -> bool:
    import jax

    from repro.kernels import pallas_collective
    from repro.launch.mesh import auto_mesh
    from repro.launch.offload_runtime import build_offload_engine
    from repro.offload.planner import build_plan

    p = len(devs)
    flat = auto_mesh((p,), ("i",), devices=devs)
    grid = auto_mesh((2, p // 2), ("a", "b"), devices=devs)
    eng = build_offload_engine(retune_on_remesh=False)
    all_ok = True
    for nbytes in sizes:
        ints, floats = payloads(rng, p, nbytes)
        for coll, op in CASES:
            x = None if coll == "BARRIER" else (floats if op == "max" else ints)
            desc = _descriptor(eng, coll, op, x, p=p)
            sim = np.asarray(eng.offload(
                desc, None if x is None else jax.device_put(x, devs[0])
            ))
            ok = np.array_equal(sim, reference(coll, op, x, p, root=p - 1))
            spmd, first_s = _timed(
                lambda: eng.offload(desc, x, axis_name="i", mesh=flat)
            )
            spmd_ok = np.array_equal(spmd, sim)
            # the planned two-level schedule over the (2, 2) mesh
            pdesc = _descriptor(eng, coll, op, x, axes=(2, p // 2))
            planned, pfirst_s = _timed(lambda: eng.offload(
                pdesc, x, axis_name=("a", "b"), mesh=grid
            ))
            planned_ok = np.array_equal(planned, sim)
            ok &= spmd_ok and planned_ok
            # what the fused backend says of the spmd form on this chip
            plan = build_plan(coll, (p,), op, 4 if x is None else x[0].nbytes)
            _, reason = pallas_collective.supports_plan(plan, ("i",))
            log("mesh", ok=bool(ok), coll=coll, op=op, bytes_per_rank=nbytes,
                spmd_ok=bool(spmd_ok), spmd_first_s=first_s,
                planned_ok=bool(planned_ok), planned_first_s=pfirst_s,
                pallas_spmd_form=reason or "admitted")
            all_ok &= bool(ok)
    t = eng.telemetry.snapshot()
    log("mesh", ok=bool(all_ok), compiles=t["compiles"], hits=t["hits"],
        backend_fallbacks=t["backend_fallbacks"])
    return bool(all_ok)


def phase_mesh_broker(rng, devs) -> bool:
    from repro.launch.mesh import auto_mesh
    from repro.launch.offload_runtime import build_offload_service

    mesh = auto_mesh((len(devs),), ("i",), devices=devs)
    svc = build_offload_service(
        axis_name="i", mesh=mesh, registry=SCRATCH / "registry",
        retune_on_remesh=False,
    )
    stats = _serve(svc, rng, p=len(devs))
    ok = stats["answered"] == stats["asked"] and not stats["errors"]
    log("mesh_broker", ok=bool(ok), mode="driver", **stats)
    return bool(ok)


def phase_mesh_dp_step(devs, seed: int, steps: int = 2) -> bool:
    import jax

    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, batches
    from repro.launch.mesh import auto_mesh
    from repro.launch.offload_runtime import build_offload_engine
    from repro.launch.steps import build_dp_train_step
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig, init_opt_state
    from repro.sharding.specs import make_topology

    mesh = auto_mesh((2, 2), ("pod", "data"), devices=devs)
    topo = make_topology(mesh)
    cfg = get_config("mamba2-130m")
    api = build_model(cfg)
    batch = 2 * len(devs)
    shape = ShapeConfig("smoke", TRAIN_SEQ, batch, "train")
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=100)
    eng = build_offload_engine(retune_on_remesh=False)
    raw_fn, _, _ = build_dp_train_step(api, topo, shape, opt, engine=None)
    off_fn, _, _ = build_dp_train_step(api, topo, shape, opt, engine=eng)
    data = batches(DataConfig(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                              global_batch=batch, seed=seed))

    def fresh():
        params = api.init(jax.random.key(seed))
        return params, init_opt_state(params)

    (p_raw, o_raw), (p_off, o_off) = fresh(), fresh()
    same, times = True, []
    for _ in range(steps):
        b = next(data)
        t0 = time.perf_counter()
        p_off, o_off, m_off = off_fn(p_off, o_off, b)
        jax.block_until_ready(m_off)
        times.append(time.perf_counter() - t0)
        p_raw, o_raw, m_raw = raw_fn(p_raw, o_raw, b)
        same &= float(m_off["loss"]) == float(m_raw["loss"])
        same &= all(
            np.array_equal(np.asarray(u), np.asarray(v))
            for u, v in zip(jax.tree.leaves(p_off), jax.tree.leaves(p_raw))
        )
    ok = same and np.isfinite(float(m_off["loss"]))
    t = eng.telemetry.snapshot()
    log("mesh_dp_step", ok=bool(ok), arch="mamba2-130m", mesh="pod=2,data=2",
        batch=batch, seq=TRAIN_SEQ, bitwise_vs_raw=bool(same),
        loss=float(m_off["loss"]), step_s=times, compiles=t["compiles"],
        hits=t["hits"],
        peak_bytes_in_use=_peak_bytes(devs[0]))
    return bool(ok)


# ---------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devs[0].platform}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 2
    devs = devs[: args.chips]

    from repro.launch.compile_cache import cache_stats, enable_compile_cache

    cache_dir = enable_compile_cache()
    SCRATCH.mkdir(exist_ok=True)
    log("setup", ok=True, platform=devs[0].platform,
        kind=devs[0].device_kind, count=len(devs), jax=jax.__version__,
        compile_cache=cache_dir, seed=args.seed)

    rng = np.random.default_rng(args.seed)
    if args.chips == 1:
        phases = [
            ("kernels", lambda: phase_kernels(rng)),
            ("engine", lambda: phase_engine(rng)),
            ("broker", lambda: phase_broker(rng)),
            ("trainer", lambda: phase_trainer(devs[0])),
        ]
    else:
        phases = [
            ("mesh", lambda: phase_mesh_collectives(rng, devs)),
            ("mesh_broker", lambda: phase_mesh_broker(rng, devs)),
            ("mesh_dp_step", lambda: phase_mesh_dp_step(devs, args.seed)),
        ]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            ok = run()
        except Exception:  # noqa: BLE001 - a phase that raises has failed
            traceback.print_exc()
            ok = False
        log(name, ok=bool(ok), done=True, seconds=time.perf_counter() - t0)
        if not ok:
            failed.append(name)
    stats = cache_stats()
    log("compile_cache", ok=True, dir=cache_dir, hits=stats["hits"],
        misses=stats["misses"])
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
