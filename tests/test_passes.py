"""Plan-optimizer pass-pipeline tests: fused-vs-unfused bitwise equivalence
for every CollType / axis order / operator family, size-1 dead-phase
regression, fusion structure and round accounting, the optimized-plan cache
key (compile-count shrink), the fusion-winner tuning hook, the broker's
mixed-flag guard, and the profiler-sourced device telemetry.

Bitwise equality across different combine trees requires exact arithmetic;
value strategies stick to integers and powers of two (and, for flash, a
shared running max so every rescale factor is exactly 1.0), exactly like
the planner tests.
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    SSD,
    CollType,
    get_operator,
    sim_scan,
)
from repro.core.selector import set_active_tuning
from repro.offload import (
    OffloadEngine,
    PhaseKind,
    TuningCache,
    build_plan,
    choose_optimization,
    eliminate_dead_phases,
    fuse_scan_total,
    lower_sim,
    optimize_plan,
    plan_comm_rounds,
    plan_layout_moves,
    tune_fusion,
)
from repro.service import DescriptorBroker
from hypothesis import given, settings, strategies as st

MESHES = [(2, 4), (4, 2), (2, 2), (3, 2), (2, 2, 2), (2, 3, 2), (1, 4),
          (2, 1, 2), (4,), (1,)]


@pytest.fixture(autouse=True)
def _no_active_tuning():
    set_active_tuning(None)
    yield
    set_active_tuning(None)


def _orders(k, idx):
    import itertools

    perms = list(itertools.permutations(range(k)))
    return perms[idx % len(perms)]


# ------------------------------------------------- bitwise: fused == unfused


@settings(max_examples=40, deadline=None)
@given(
    mesh_idx=st.integers(0, len(MESHES) - 1),
    coll_idx=st.integers(0, len(CollType) - 1),
    order_idx=st.integers(0, 5),
    seed=st.integers(0, 10_000),
)
def test_optimized_bitwise_equals_unfused_all_colltypes(
    mesh_idx, coll_idx, order_idx, seed
):
    """Every CollType, every 1-3-axis mesh/order: the optimized plan's
    result equals the unoptimized plan's AND the flat reference, bit for
    bit (integer payloads)."""
    sizes = MESHES[mesh_idx]
    coll = list(CollType)[coll_idx].name
    order = _orders(len(sizes), order_idx)
    p = int(np.prod(sizes))
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(-6, 7, size=(p, 5)).astype(np.float32))
    root = seed % p
    raw = build_plan(coll, sizes, "sum", 20, order=order, root=root)
    opt = optimize_plan(raw)
    arg = None if coll == "BARRIER" else x
    got_raw = np.asarray(lower_sim(raw)(arg))
    got_opt = np.asarray(lower_sim(opt)(arg))
    np.testing.assert_array_equal(got_opt, got_raw)


@settings(max_examples=24, deadline=None)
@given(
    mesh_idx=st.integers(0, 4),
    inclusive=st.booleans(),
    order_idx=st.integers(0, 5),
    seed=st.integers(0, 10_000),
)
def test_optimized_ssd_bitwise(mesh_idx, inclusive, order_idx, seed):
    """Non-commutative SSD (decay, state) recurrence: fused == unfused
    bitwise for inclusive and exclusive scans, every axis order."""
    sizes = [(2, 4), (4, 2), (2, 2, 2), (3, 2), (2, 1, 2)][mesh_idx]
    order = _orders(len(sizes), order_idx)
    p = int(np.prod(sizes))
    rng = np.random.default_rng(seed)
    a = jnp.asarray(
        rng.choice([0.5, 1.0, 2.0], size=(p, 4)).astype(np.float32)
    )
    b = jnp.asarray(rng.integers(-4, 5, size=(p, 4)).astype(np.float32))
    coll = "SCAN" if inclusive else "EXSCAN"
    raw = build_plan(coll, sizes, SSD, 32, order=order)
    opt = optimize_plan(raw)
    ra, rb = lower_sim(raw, SSD)((a, b))
    oa, ob = lower_sim(opt, SSD)((a, b))
    np.testing.assert_array_equal(np.asarray(oa), np.asarray(ra))
    np.testing.assert_array_equal(np.asarray(ob), np.asarray(rb))


@settings(max_examples=16, deadline=None)
@given(
    mesh_idx=st.integers(0, 3),
    inclusive=st.booleans(),
    m_val=st.integers(-3, 3),
    seed=st.integers(0, 10_000),
)
def test_optimized_flash_bitwise(mesh_idx, inclusive, m_val, seed):
    """Flash-attention combine (m, l, o): with a shared running max every
    rescale is exp(0) == 1.0 exactly, so fused == unfused bitwise."""
    sizes = [(2, 4), (4, 2), (2, 2, 2), (2, 3)][mesh_idx]
    p = int(np.prod(sizes))
    flash = get_operator("flash")
    rng = np.random.default_rng(seed)
    m = jnp.full((p, 4), float(m_val), jnp.float32)
    l = jnp.asarray(rng.integers(1, 6, size=(p, 4)).astype(np.float32))
    o = jnp.asarray(rng.integers(-5, 6, size=(p, 4)).astype(np.float32))
    coll = "SCAN" if inclusive else "EXSCAN"
    raw = build_plan(coll, sizes, flash, 48, order="auto")
    opt = optimize_plan(raw)
    got_raw = lower_sim(raw, flash)((m, l, o))
    got_opt = lower_sim(opt, flash)((m, l, o))
    for g, w in zip(got_opt, got_raw):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ------------------------------------------------------ pass structure


def test_fusion_produces_fused_phases_and_fewer_rounds():
    raw = build_plan("SCAN", (2, 4), "sum", 16, order=(0, 1))
    opt = optimize_plan(raw)
    assert opt.optimized and not raw.optimized
    kinds = [ph.kind for ph in opt.phases]
    assert kinds[0] == PhaseKind.FUSED_SCAN_TOTAL
    fused = opt.phases[0]
    assert fused.dst == "y" and fused.dst2 == "t" and fused.src == ("x",)
    assert plan_comm_rounds(opt) < plan_comm_rounds(raw)
    # 3-axis SCAN fuses at two ladder levels
    opt3 = optimize_plan(build_plan("SCAN", (2, 2, 2), "sum", 16,
                                    order=(0, 1, 2)))
    assert sum(
        ph.kind == PhaseKind.FUSED_SCAN_TOTAL for ph in opt3.phases
    ) == 2
    # EXSCAN reduces rounds even on the CI 2x2 mesh
    raw22 = build_plan("EXSCAN", (2, 2), "sum", 16, order=(0, 1))
    assert plan_comm_rounds(optimize_plan(raw22)) < plan_comm_rounds(raw22)


def test_fusion_requires_same_source_register():
    """A TOTAL reading a different register than the SCAN must not fuse."""
    raw = build_plan("SCAN", (2, 4), "sum", 16, order=(0, 1))
    scan_ph = raw.phases[0]
    hacked = dataclasses.replace(
        raw,
        phases=(scan_ph,)
        + (dataclasses.replace(raw.phases[1], src=(scan_ph.dst,)),)
        + raw.phases[2:],
    )
    fused = fuse_scan_total(hacked)
    assert all(
        ph.kind != PhaseKind.FUSED_SCAN_TOTAL for ph in fused.phases
    )


def test_size_one_axes_produce_zero_phases():
    """Dead-phase elimination: no optimized phase may span a size-1 level,
    and degenerate meshes collapse to zero communication phases."""
    comm_kinds = (
        PhaseKind.SCAN, PhaseKind.TOTAL, PhaseKind.REDUCE,
        PhaseKind.BARRIER, PhaseKind.FUSED_SCAN_TOTAL,
    )
    for sizes in [(1, 4), (4, 1), (2, 1, 2), (1, 1), (1,), (1, 1, 3)]:
        for coll in [c.name for c in CollType]:
            opt = optimize_plan(
                build_plan(coll, sizes, "sum", 16,
                           order=tuple(range(len(sizes))))
            )
            for ph in opt.phases:
                if ph.level >= 0:
                    assert opt.logical_sizes[ph.level] > 1, (coll, sizes, ph)
    # a (1, 4) scan is exactly the (4,) scan: one communication phase
    opt = optimize_plan(build_plan("SCAN", (1, 4), "sum", 16, order=(0, 1)))
    assert len(opt.phases) == 1 and opt.phases[0].kind == PhaseKind.SCAN
    # an all-ones mesh has no communication at all
    for coll in [c.name for c in CollType]:
        opt = optimize_plan(
            build_plan(coll, (1, 1), "sum", 16, order=(0, 1))
        )
        assert not [p for p in opt.phases if p.kind in comm_kinds], coll
    # ... and an all-ones EXSCAN still materializes the identity
    opt = optimize_plan(build_plan("EXSCAN", (1, 1), "sum", 16, order=(0, 1)))
    assert [ph.kind for ph in opt.phases] == [PhaseKind.IDENTITY]
    x = jnp.asarray([[3.0, 4.0]])
    np.testing.assert_array_equal(np.asarray(lower_sim(opt)(x)), 0.0)


def test_optimize_plan_idempotent_and_validates_pass_names():
    raw = build_plan("EXSCAN", (2, 2, 2), "sum", 16, order=(0, 1, 2))
    opt = optimize_plan(raw)
    again = optimize_plan(opt)
    assert again.phases == opt.phases and again.result == opt.result
    with pytest.raises(ValueError, match="unknown passes"):
        optimize_plan(raw, passes=("nope",))
    # dead-phase elimination alone keeps the plan unoptimized (no wire flag)
    dpe = eliminate_dead_phases(raw)
    assert not dpe.optimized


def test_describe_renders_fused_phases_and_per_plan_permute_chain():
    opt = optimize_plan(build_plan("SCAN", (2, 2, 2), "sum", 16,
                                   order=(0, 1, 2)))
    text = opt.describe()
    assert "[optimized]" in text
    assert "fused_scan_total" in text and "-> y, t" in text
    assert "permute chain (once per plan" in text
    # view sharing: the threaded chain beats the per-phase front-and-back
    # chain on the raw plan (SCAN + TOTAL share their operand's view) and
    # never exceeds it on the fused plan
    raw = build_plan("SCAN", (2, 2, 2), "sum", 16, order=(0, 1, 2))
    threaded_raw = plan_layout_moves(dataclasses.replace(raw, optimized=True))
    assert len(threaded_raw) < len(plan_layout_moves(raw))
    unthreaded_opt = plan_layout_moves(
        dataclasses.replace(opt, optimized=False)
    )
    assert len(plan_layout_moves(opt)) <= len(unthreaded_opt)
    # unoptimized plans keep the classic per-phase rendering
    raw_text = build_plan("SCAN", (2, 2), "sum", 16, order=(0, 1)).describe()
    assert "permute chain" not in raw_text


# ----------------------------------------------- engine: flag + cache key


def test_engine_optimized_dispatch_matches_and_dedups_compiles():
    eng = OffloadEngine()
    p = 8
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-5, 6, size=(p, 6)).astype(np.float32))
    want = np.asarray(sim_scan(x, "sum", p, algorithm="hillis_steele"))
    d_opt = eng.make_descriptor(
        "SCAN", axes=(2, 2, 2), payload_bytes=24, op="sum", optimize=True
    )
    assert d_opt.optimized
    assert len(d_opt.encode()) == 16
    np.testing.assert_array_equal(np.asarray(eng.offload(d_opt, x)), want)
    # optimized vs raw are distinct compiled schedules
    d_raw = dataclasses.replace(d_opt, optimized=False)
    np.testing.assert_array_equal(np.asarray(eng.offload(d_raw, x)), want)
    assert eng.telemetry.misses == 2
    # same optimized plan from another comm_id: cache HIT, no new compile
    np.testing.assert_array_equal(
        np.asarray(eng.offload(dataclasses.replace(d_opt, comm_id=7), x)),
        want,
    )
    assert (eng.telemetry.misses, eng.telemetry.compiles) == (2, 2)
    assert eng.telemetry.hits == 1
    # (2,4) split (1,0) and (4,2) split (0,1) share one logical plan
    e2 = OffloadEngine()
    da = e2.make_descriptor("SCAN", axes=(2, 4), payload_bytes=24,
                            op="sum", split=(1, 0), optimize=True)
    db = e2.make_descriptor("SCAN", axes=(4, 2), payload_bytes=24,
                            op="sum", split=(0, 1), optimize=True)
    ya = np.asarray(e2.offload(da, x))
    yb = np.asarray(e2.offload(db, x))
    np.testing.assert_array_equal(ya, yb)
    assert (e2.telemetry.misses, e2.telemetry.hits) == (1, 1)


def test_engine_clear_drops_plan_memos():
    eng = OffloadEngine()
    x = jnp.ones((4, 2), jnp.float32)
    d = eng.make_descriptor("SCAN", axes=(2, 2), payload_bytes=8,
                            op="sum", optimize=True)
    eng.offload(d, x)
    assert eng._plan_memo and eng._plans
    eng.clear()
    assert not eng._plan_memo and not eng._plans
    assert eng.telemetry.cache_clears == 1


# ------------------------------------------------ tuning: fusion winners


def test_choose_optimization_prefers_measured_winner():
    sizes, payload = (2, 4), 1024
    # cost model says optimize (fewer rounds at equal-or-better cost)
    assert choose_optimization("EXSCAN", sizes, payload) is True
    # a measured winner saying "unfused" overrides the model
    cache = TuningCache(backend="synthetic")
    cache.record_fusion("exscan", sizes, True, payload, 9e-6)
    cache.record_fusion("exscan", sizes, False, payload, 1e-6)
    cache.activate()
    assert choose_optimization("EXSCAN", sizes, payload) is False
    # nearby payloads snap to the same winner; untuned shapes fall back
    assert choose_optimization("EXSCAN", sizes, 2048) is False
    assert choose_optimization("EXSCAN", (2, 2, 2), payload) is True
    set_active_tuning(None)
    assert choose_optimization("EXSCAN", sizes, payload) is True


def test_tune_fusion_records_both_forms_and_roundtrips(tmp_path):
    cache = tune_fusion(
        topologies=[(2, 2)], payloads=(256,), colls=("scan",), iters=1
    )
    assert ("scan", (2, 2), 256) in cache.fusion_winners
    forms = {
        m.optimized
        for m in cache.fusion_measurements
        if (m.coll, m.sizes, m.payload_bytes) == ("scan", (2, 2), 256)
    }
    assert forms == {False, True}
    path = cache.save(tmp_path / "table.json")
    loaded = TuningCache.load(path)
    assert loaded.fusion_winners == cache.fusion_winners
    # merge keeps the lower measurement per (coll, sizes, flag, payload)
    other = TuningCache(backend=cache.backend)
    other.record_fusion("scan", (2, 2), True, 256, 0.0)
    merged = loaded.merge(other)
    assert merged.fusion_winner("scan", (2, 2), 256) is True


# --------------------------------------------------- broker: mixed flags


def test_broker_rejects_mixed_optimizer_flag_groups():
    broker = DescriptorBroker(OffloadEngine())
    client = broker.client("t0")
    x = jnp.ones((4, 2), jnp.float32)
    d_opt = broker.make_descriptor(
        "ALLREDUCE", axes=(2, 2), payload_bytes=8, op="sum", optimize=True
    )
    d_raw = dataclasses.replace(d_opt, optimized=False)
    # normal grouping never mixes: the flag is in the normalized words
    t1 = client.submit(d_opt.encode(), x)
    t2 = client.submit(d_raw.encode(), x)
    broker.drain()
    np.testing.assert_array_equal(
        np.asarray(t1.result(10)), np.asarray(t2.result(10))
    )
    snap = broker.telemetry.snapshot()
    assert snap["flushes"] >= 2  # two groups, not one fused dispatch
    # the defensive guard on a hand-built mixed group fails the tickets
    import time

    from repro.service.broker import _Request, ServiceTicket

    now = time.monotonic()
    reqs = [
        _Request("t0", d, x, ServiceTicket("t0", i), now, now, None)
        for i, d in enumerate((d_opt, d_raw))
    ]
    broker._dispatch_group(reqs)
    for r in reqs:
        with pytest.raises(ValueError, match="mixed plan-optimizer"):
            r.ticket.result(1)
    client.close()


# ------------------------------------------------ SPMD (real 2x2 mesh)


def test_fusion_spmd_driver_check(subprocess_runner):
    """Driver + spmd mode on a real 2x2 device mesh: optimized descriptors
    bitwise vs raw and vs flat for all five CollTypes, fused lower_spmd
    inside shard_map, and profiler-sourced device telemetry."""
    out = subprocess_runner("repro.testing.fusion_check", "2", "2")
    assert "fusion_check_summary,bitwise_equal,1,device_latency,1" in out


# ------------------------------------------- telemetry: device-side source


def test_record_device_latency_snapshot_fields():
    eng = OffloadEngine()
    x = jnp.ones((4, 2), jnp.float32)
    d = eng.make_descriptor("SCAN", p=4, payload_bytes=8)
    eng.offload(d, x)
    snap = eng.telemetry.snapshot()
    assert snap["latency_source_by_coll"] == {"scan": "wall"}
    assert snap["device_latency_by_coll_us"] == {}
    eng.telemetry.record_device_latency("scan", 5e-6, source="profiler")
    eng.telemetry.record_device_latency("scan", 7e-6, source="profiler")
    snap = eng.telemetry.snapshot()
    assert snap["latency_source_by_coll"]["scan"] == "profiler"
    assert snap["device_latency_by_coll_us"]["scan"] == pytest.approx(6.0)
    # a wall fallback never demotes an existing profiler source — nor
    # dilutes its mean: the labeled number stays purely device-side
    eng.telemetry.record_device_latency("scan", 9e-6, source="wall")
    snap = eng.telemetry.snapshot()
    assert snap["latency_source_by_coll"]["scan"] == "profiler"
    assert snap["device_latency_by_coll_us"]["scan"] == pytest.approx(6.0)
    # ... and the first profiler sample evicts earlier wall fallbacks
    eng.telemetry.record_device_latency("allreduce", 100e-6, source="wall")
    eng.telemetry.record_device_latency("allreduce", 2e-6, source="profiler")
    snap = eng.telemetry.snapshot()
    assert snap["latency_source_by_coll"]["allreduce"] == "profiler"
    assert snap["device_latency_by_coll_us"]["allreduce"] == pytest.approx(
        2.0
    )
