"""Collective-planner tests: N-level plans for every CollType vs the flat
single-axis reference (bitwise), tuned axis splits, descriptor topology
round-trips, planned engine dispatch, and the fault-driven re-plan hook.

Bitwise equality across different combine trees requires exact arithmetic;
the value strategies below stick to integers and powers of two (and, for
flash, a shared running max so every rescale factor is exactly 1.0), so any
association of the operator gives identical bits.
"""

import dataclasses
import warnings

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    SSD,
    CollType,
    CollectiveDescriptor,
    get_operator,
    sim_allreduce,
    sim_barrier,
    sim_reduce,
    sim_scan,
)
from repro.core.selector import set_active_tuning
from repro.offload import (
    OffloadEngine,
    PlanLayout,
    TuningCache,
    build_plan,
    lower_sim,
    plan_axis_order,
    plan_cost,
    plan_layout,
    tune_splits,
)
from repro.sharding.specs import plan_spec
from hypothesis import given, settings, strategies as st

MESHES_2D = [(2, 4), (4, 2), (3, 3), (2, 2)]
MESHES_3D = [(2, 2, 2), (2, 3, 2), (3, 2, 2)]


@pytest.fixture(autouse=True)
def _no_active_tuning():
    set_active_tuning(None)
    yield
    set_active_tuning(None)


def _flat_reference(coll, x, p, *, root=0):
    if coll == "SCAN":
        return sim_scan(x, "sum", p, algorithm="hillis_steele")
    if coll == "EXSCAN":
        return sim_scan(
            x, "sum", p, algorithm="hillis_steele", inclusive=False
        )
    if coll == "REDUCE":
        return sim_reduce(x, "sum", p, root=root)
    if coll == "ALLREDUCE":
        return sim_allreduce(x, "sum", p)
    return sim_barrier(p)


# ----------------------------------------------------------- plan vs flat


@pytest.mark.parametrize("sizes", MESHES_2D + MESHES_3D)
@pytest.mark.parametrize("coll", [c.name for c in CollType])
def test_planned_matches_flat_bitwise_all_colltypes(sizes, coll):
    """Every CollType, every 2D/3D mesh shape: the planned result equals the
    flat single-axis reference bit for bit (integer payloads)."""
    p = int(np.prod(sizes))
    rng = np.random.default_rng(p * 7 + len(sizes))
    x = jnp.asarray(rng.integers(-6, 7, size=(p, 5)).astype(np.float32))
    root = p - 2 if p > 2 else 0
    plan = build_plan(coll, sizes, "sum", 20, order="auto", root=root)
    got = lower_sim(plan)(None if coll == "BARRIER" else x)
    want = _flat_reference(coll, x, p, root=root)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("sizes", [(2, 4), (2, 2, 2)])
def test_planned_every_split_same_result(sizes):
    """All axis orders of one mesh produce the same (flat-reference) bits —
    the split changes the schedule, never the answer."""
    import itertools

    p = int(np.prod(sizes))
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-5, 6, size=(p, 4)).astype(np.float32))
    want = np.asarray(sim_scan(x, "sum", p, algorithm="hillis_steele"))
    for order in itertools.permutations(range(len(sizes))):
        plan = build_plan("SCAN", sizes, "sum", 16, order=order)
        got = np.asarray(lower_sim(plan)(x))
        np.testing.assert_array_equal(got, want, err_msg=f"order={order}")


def test_reduce_root_placement_off_rank_zero():
    for sizes in [(2, 4), (2, 2, 2), (3, 3)]:
        p = int(np.prod(sizes))
        rng = np.random.default_rng(p)
        x = jnp.asarray(rng.integers(-9, 10, size=(p, 3)).astype(np.float32))
        for root in range(p):
            plan = build_plan("REDUCE", sizes, "sum", 12, root=root)
            got = np.asarray(lower_sim(plan)(x))
            want = np.asarray(sim_reduce(x, "sum", p, root=root))
            np.testing.assert_array_equal(
                got, want, err_msg=f"sizes={sizes} root={root}"
            )


def test_reduce_off_root_under_non_identity_split():
    """REDUCE to an off-rank-0 root with every *non-identity* axis order —
    the trainer-path edge case: the split must not move the root."""
    import itertools

    for sizes in [(2, 4), (2, 2, 2), (3, 2, 2)]:
        p = int(np.prod(sizes))
        rng = np.random.default_rng(p * 13)
        x = jnp.asarray(rng.integers(-7, 8, size=(p, 4)).astype(np.float32))
        orders = [
            o
            for o in itertools.permutations(range(len(sizes)))
            if o != tuple(range(len(sizes)))
        ]
        for order in orders:
            for root in (1, p - 2, p - 1):
                plan = build_plan(
                    "REDUCE", sizes, "sum", 16, order=order, root=root
                )
                got = np.asarray(lower_sim(plan)(x))
                want = np.asarray(sim_reduce(x, "sum", p, root=root))
                np.testing.assert_array_equal(
                    got, want, err_msg=f"sizes={sizes} order={order} "
                    f"root={root}"
                )


def test_exscan_3d_mesh_bitwise_all_orders():
    """EXSCAN over 3D meshes, every axis order, vs the flat single-axis
    reference — bit for bit (integer payloads)."""
    import itertools

    for sizes in MESHES_3D:
        p = int(np.prod(sizes))
        rng = np.random.default_rng(p * 31)
        x = jnp.asarray(rng.integers(-6, 7, size=(p, 5)).astype(np.float32))
        want = np.asarray(
            sim_scan(x, "sum", p, algorithm="hillis_steele", inclusive=False)
        )
        for order in itertools.permutations(range(3)):
            plan = build_plan("EXSCAN", sizes, "sum", 20, order=order)
            got = np.asarray(lower_sim(plan)(x))
            np.testing.assert_array_equal(
                got, want, err_msg=f"sizes={sizes} order={order}"
            )


# -------------------------------------------- hypothesis: non-commutative


@settings(max_examples=24, deadline=None)
@given(
    mesh_idx=st.integers(0, 4),
    inclusive=st.booleans(),
    seed=st.integers(0, 10_000),
)
def test_planned_ssd_bitwise_equivalence(mesh_idx, inclusive, seed):
    """SSD (non-commutative (decay, state) recurrence): planned == flat
    bitwise, using exact arithmetic (power-of-two decays, integer states)."""
    sizes = [(2, 4), (4, 2), (2, 2, 2), (3, 2), (2, 3, 2)][mesh_idx]
    p = int(np.prod(sizes))
    rng = np.random.default_rng(seed)
    a = jnp.asarray(
        rng.choice([0.5, 1.0, 2.0], size=(p, 4)).astype(np.float32)
    )
    b = jnp.asarray(rng.integers(-4, 5, size=(p, 4)).astype(np.float32))
    coll = "SCAN" if inclusive else "EXSCAN"
    plan = build_plan(coll, sizes, SSD, 32, order="auto")
    ga, gb = lower_sim(plan, SSD)((a, b))
    wa, wb = sim_scan(
        (a, b), SSD, p, algorithm="hillis_steele", inclusive=inclusive
    )
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(wb))


@settings(max_examples=16, deadline=None)
@given(
    mesh_idx=st.integers(0, 3),
    inclusive=st.booleans(),
    m_val=st.integers(-3, 3),
    seed=st.integers(0, 10_000),
)
def test_planned_flash_bitwise_equivalence(mesh_idx, inclusive, m_val, seed):
    """Flash-attention combine (m, l, o): with a shared running max every
    rescale is exp(0) == 1.0 exactly, so planned == flat bitwise."""
    sizes = [(2, 4), (4, 2), (2, 2, 2), (2, 3)][mesh_idx]
    p = int(np.prod(sizes))
    flash = get_operator("flash")
    rng = np.random.default_rng(seed)
    m = jnp.full((p, 4), float(m_val), jnp.float32)
    l = jnp.asarray(rng.integers(1, 6, size=(p, 4)).astype(np.float32))
    o = jnp.asarray(rng.integers(-5, 6, size=(p, 4)).astype(np.float32))
    coll = "SCAN" if inclusive else "EXSCAN"
    plan = build_plan(coll, sizes, flash, 48, order="auto")
    got = lower_sim(plan, flash)((m, l, o))
    want = sim_scan(
        (m, l, o), flash, p, algorithm="hillis_steele", inclusive=inclusive
    )
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@settings(max_examples=12, deadline=None)
@given(
    mesh_idx=st.integers(0, 2),
    root_frac=st.integers(0, 100),
    seed=st.integers(0, 10_000),
)
def test_planned_reduce_ssd_any_root(mesh_idx, root_frac, seed):
    """REDUCE of the non-commutative SSD operator to an arbitrary root."""
    sizes = [(2, 4), (2, 2, 2), (3, 2)][mesh_idx]
    p = int(np.prod(sizes))
    root = root_frac % p
    rng = np.random.default_rng(seed)
    a = jnp.asarray(
        rng.choice([0.5, 1.0, 2.0], size=(p, 3)).astype(np.float32)
    )
    b = jnp.asarray(rng.integers(-3, 4, size=(p, 3)).astype(np.float32))
    plan = build_plan("REDUCE", sizes, SSD, 24, root=root)
    ga, gb = lower_sim(plan, SSD)((a, b))
    wa, wb = sim_reduce((a, b), SSD, p, root=root)
    np.testing.assert_array_equal(np.asarray(ga), np.asarray(wa))
    np.testing.assert_array_equal(np.asarray(gb), np.asarray(wb))


# ------------------------------------------------------- tuned axis split


def test_plan_axis_order_is_a_permutation_and_deterministic():
    for sizes in [(2, 4), (4, 2), (2, 2, 2), (8, 2)]:
        order = plan_axis_order("SCAN", sizes, 1024)
        assert sorted(order) == list(range(len(sizes)))
        assert order == plan_axis_order("SCAN", sizes, 1024)


def test_split_winner_overrides_model_choice():
    """A measured split winner in the active table rules over the cost
    model's preference."""
    model_choice = plan_axis_order("SCAN", (2, 4), 1024)
    forced = tuple(reversed(model_choice))
    cache = TuningCache(backend="synthetic")
    cache.record_split("scan", (2, 4), forced, 1024, 1e-6)
    cache.record_split("scan", (2, 4), model_choice, 1024, 9e-6)
    cache.activate()
    assert plan_axis_order("SCAN", (2, 4), 1024) == forced
    # nearby payloads snap to the measured winner too
    assert plan_axis_order("SCAN", (2, 4), 2048) == forced
    # a shape never split-tuned falls back to the model
    assert sorted(plan_axis_order("SCAN", (2, 2, 2), 1024)) == [0, 1, 2]
    set_active_tuning(None)
    assert plan_axis_order("SCAN", (2, 4), 1024) == model_choice


def test_tune_splits_records_winners_and_json_roundtrip(tmp_path):
    cache = tune_splits(
        topologies=[(2, 2)], payloads=(256,), colls=("scan",), iters=1
    )
    assert ("scan", (2, 2), 256) in cache.split_winners
    winner = cache.split_winner("scan", (2, 2), 256)
    assert winner in [(0, 1), (1, 0)]
    path = cache.save(tmp_path / "table.json")
    loaded = TuningCache.load(path)
    assert loaded.split_winners == cache.split_winners
    # the recorded winner is the measured minimum over all orders
    by_order = {
        m.order: m.seconds
        for m in cache.split_measurements
        if (m.coll, m.sizes, m.payload_bytes) == ("scan", (2, 2), 256)
    }
    assert by_order[winner] == min(by_order.values())


def test_plan_cost_positive_and_order_sensitive():
    plan_a = build_plan("SCAN", (2, 8), "sum", 4096, order=(0, 1))
    plan_b = build_plan("SCAN", (2, 8), "sum", 4096, order=(1, 0))
    assert plan_cost(plan_a, 4096) > 0
    assert plan_cost(plan_b, 4096) > 0
    assert plan_cost(plan_a, 4096) != plan_cost(plan_b, 4096)


def test_build_plan_validation():
    with pytest.raises(ValueError, match="permutation"):
        build_plan("SCAN", (2, 4), "sum", 16, order=(0, 0))
    with pytest.raises(ValueError, match="root"):
        build_plan("REDUCE", (2, 4), "sum", 16, root=99)
    with pytest.raises(ValueError, match="mesh axes"):
        build_plan("SCAN", (2, 2, 2, 2), "sum", 16)


# ----------------------------------------------------- plan layout helper


@settings(max_examples=40, deadline=None)
@given(
    k=st.integers(1, 3),
    perm_idx=st.integers(0, 5),
    sizes_seed=st.integers(0, 1000),
)
def test_plan_layout_roundtrip_property(k, perm_idx, sizes_seed):
    """layout.to_logical o layout.to_physical == identity (and vice versa)
    for every permutation of <= 3 axes."""
    import itertools

    rng = np.random.default_rng(sizes_seed)
    sizes = tuple(int(s) for s in rng.integers(1, 5, size=k))
    perms = list(itertools.permutations(range(k)))
    order = perms[perm_idx % len(perms)]
    layout = PlanLayout(sizes=sizes, order=order)
    p = int(np.prod(sizes))
    x = rng.normal(size=(p, 3)).astype(np.float32)
    np.testing.assert_array_equal(layout.to_logical(layout.to_physical(x)), x)
    np.testing.assert_array_equal(layout.to_physical(layout.to_logical(x)), x)
    # the flat permutation agrees with the reshape/transpose path
    perm = layout.permutation()
    assert sorted(perm.tolist()) == list(range(p))
    np.testing.assert_array_equal(x[perm], layout.to_physical(x))


def test_plan_layout_from_plan_and_descriptor():
    plan = build_plan("SCAN", (2, 4), "sum", 16, order=(1, 0))
    layout = plan_layout(plan)
    assert layout.sizes == (2, 4)
    assert layout.order == (1, 0)
    assert layout.logical_sizes == (4, 2)
    assert layout.inverse == (1, 0)
    d = CollectiveDescriptor(
        comm_size=8, coll_type=CollType.SCAN, algo_type="hillis_steele",
        axes=(2, 2, 2), split=(1, 2, 0),
    )
    dl = plan_layout(d)
    assert dl.sizes == (2, 2, 2) and dl.order == (1, 2, 0)
    # identity order when the descriptor carries no split
    d2 = CollectiveDescriptor(
        comm_size=8, coll_type=CollType.SCAN, algo_type="hillis_steele",
        axes=(2, 4),
    )
    assert plan_layout(d2).order == (0, 1)
    with pytest.raises(ValueError, match="permutation"):
        PlanLayout(sizes=(2, 4), order=(1, 1))
    with pytest.raises(ValueError, match="topology"):
        plan_layout(object())


def test_plan_spec_orders_axes_logically():
    layout = PlanLayout(sizes=(2, 2, 2), order=(1, 2, 0))
    spec = plan_spec(layout, ("pod", "outer", "inner"), ndim=2)
    assert tuple(spec) == (("outer", "inner", "pod"), None)
    single = PlanLayout(sizes=(4,), order=(0,))
    assert tuple(plan_spec(single, ("r",), ndim=1)) == ("r",)
    with pytest.raises(ValueError, match="cover"):
        plan_spec(layout, ("pod", "outer"))


# -------------------------------------------- descriptor topology encoding


def test_descriptor_topology_roundtrip():
    d = CollectiveDescriptor(
        comm_size=8,
        coll_type=CollType.SCAN,
        algo_type="hillis_steele",
        axes=(2, 2, 2),
        split=(1, 2, 0),
    )
    assert CollectiveDescriptor.decode(d.encode()) == d
    assert len(d.encode()) == 16


def test_descriptor_legacy_ten_word_decode():
    d = CollectiveDescriptor(comm_size=8, algo_type="hillis_steele")
    legacy = d.encode()[:10]
    assert CollectiveDescriptor.decode(legacy) == d


def test_descriptor_topology_validation():
    with pytest.raises(ValueError, match="factor"):
        CollectiveDescriptor(comm_size=8, axes=(2, 3))
    with pytest.raises(ValueError, match="permutation"):
        CollectiveDescriptor(comm_size=8, axes=(2, 4), split=(1, 1))
    with pytest.raises(ValueError, match="without axes"):
        CollectiveDescriptor(comm_size=8, split=(0, 1))


# ---------------------------------------------------- engine planned path


def test_engine_planned_dispatch_and_cache():
    eng = OffloadEngine()
    p = 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-5, 6, size=(p, 6)).astype(np.float32))
    desc = eng.make_descriptor(
        "SCAN", axes=(2, 2, 2), payload_bytes=24, op="sum"
    )
    assert desc.axes == (2, 2, 2)
    assert sorted(desc.split) == [0, 1, 2]
    assert CollectiveDescriptor.decode(desc.encode()) == desc
    want = np.asarray(sim_scan(x, "sum", p, algorithm="hillis_steele"))
    out = np.asarray(eng.offload(desc.encode(), x))
    np.testing.assert_array_equal(out, want)
    assert (eng.telemetry.hits, eng.telemetry.misses) == (0, 1)
    out = np.asarray(eng.offload(desc, x))
    np.testing.assert_array_equal(out, want)
    assert (eng.telemetry.hits, eng.telemetry.misses) == (1, 1)
    assert eng.telemetry.snapshot()["cache_size"] == 1
    # the cache keys on the plan, not the words: a reversed split of the
    # symmetric 2x2x2 mesh yields the identical logical plan -> cache HIT
    other = dataclasses.replace(desc, split=tuple(reversed(desc.split)))
    eng.offload(other, x)
    assert (eng.telemetry.hits, eng.telemetry.misses) == (2, 1)
    assert eng.telemetry.snapshot()["cache_size"] == 1
    # a split that changes the logical shape is a different compiled plan
    d24 = eng.make_descriptor(
        "SCAN", axes=(2, 4), payload_bytes=24, op="sum", split=(0, 1)
    )
    d42 = dataclasses.replace(
        d24, axes=(4, 2), split=(0, 1)  # logical (2, 4) -> (4, 2): distinct
    )
    eng.offload(d24, x)
    eng.offload(d42, x)
    assert eng.telemetry.misses == 3
    assert eng.telemetry.snapshot()["cache_size"] == 3


def test_engine_planned_all_colltypes_match_flat():
    eng = OffloadEngine()
    axes = (2, 2, 2)
    p = 8
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.integers(-5, 6, size=(p, 4)).astype(np.float32))
    for coll in CollType:
        desc = eng.make_descriptor(
            coll.name, axes=axes, payload_bytes=16, op="sum", root=5
        )
        got = np.asarray(
            eng.offload(desc, None if coll == CollType.BARRIER else x)
        )
        want = np.asarray(_flat_reference(coll.name, x, p, root=5))
        np.testing.assert_array_equal(got, want, err_msg=coll.name)
    snap = eng.telemetry.snapshot()
    assert snap["cache_size"] == len(CollType)
    assert set(snap["latency_by_coll_us"]) == {
        c.name.lower() for c in CollType
    }
    assert all(v > 0 for v in snap["latency_by_coll_us"].values())


def test_engine_telemetry_latency_by_coll():
    eng = OffloadEngine()
    x = jnp.ones((4, 2), jnp.float32)
    d1 = eng.make_descriptor("SCAN", p=4, payload_bytes=8)
    d2 = eng.make_descriptor("ALLREDUCE", p=4, payload_bytes=8)
    for _ in range(3):
        eng.offload(d1, x)
    eng.offload(d2, x)
    snap = eng.telemetry.snapshot()
    assert snap["calls_by_coll"] == {"scan": 3, "allreduce": 1}
    assert snap["latency_by_coll_us"]["scan"] > 0
    assert snap["latency_by_coll_us"]["allreduce"] > 0
    assert snap["cache_size"] == 2


# ------------------------------------------------ fingerprint-checked load


def test_load_compatible_rejects_foreign_backend_with_warning(tmp_path):
    cache = TuningCache(backend="cuda:H100:x86_64")
    cache.record("scan", "hillis_steele", 4, 1024, 5e-6)
    path = cache.save(tmp_path / "foreign.json")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loaded = TuningCache.load_compatible(path)
    assert loaded is None
    assert any("backend" in str(w.message) for w in caught)
    # strict load still works regardless of fingerprint
    strict = TuningCache.load(path)
    assert strict.backend == "cuda:H100:x86_64"


def test_load_compatible_accepts_same_backend(tmp_path):
    cache = TuningCache()  # current backend fingerprint
    cache.record("scan", "hillis_steele", 4, 1024, 5e-6)
    path = cache.save(tmp_path / "native.json")
    loaded = TuningCache.load_compatible(path)
    assert loaded is not None
    assert loaded.winners == cache.winners


# ------------------------------------------------- fault-driven re-planning


def test_remesh_triggers_replan_and_retune():
    from repro.launch.offload_runtime import (
        build_offload_engine,
        detach_remesh_hook,
    )
    from repro.core.selector import get_active_tuning
    from repro.runtime.fault import notify_remesh, plan_remesh

    eng = build_offload_engine(
        retune_on_remesh=True, remesh_tune_budget_s=0.05
    )
    try:
        x = jnp.ones((4, 2), jnp.float32)
        eng.offload(eng.make_descriptor("SCAN", p=4, payload_bytes=8), x)
        assert eng.cache_size() == 1
        before = get_active_tuning()
        # planning alone is a pure feasibility query — nothing invalidated
        assert plan_remesh(4, 2, lost_hosts=1) == (2, 2)
        assert eng.cache_size() == 1
        # *adopting* the plan fires the listeners
        notify_remesh((4, 2), (2, 2))
        assert eng.cache_size() == 0
        assert eng.telemetry.snapshot()["cache_size"] == 0
        after = get_active_tuning()
        assert after is not None and after is not before
        assert len(after.measurements) >= 1
    finally:
        detach_remesh_hook(eng)
        set_active_tuning(None)


def test_planner_spmd_3d_mesh(subprocess_runner):
    """All five CollTypes, engine-dispatched as planned descriptors inside
    shard_map on a real 2x2x2 (pod, outer, inner) device mesh."""
    subprocess_runner("repro.testing.planner_check", "2", "2", "2")


def test_detached_hook_no_longer_fires():
    from repro.launch.offload_runtime import (
        build_offload_engine,
        detach_remesh_hook,
    )
    from repro.runtime.fault import notify_remesh

    eng = build_offload_engine(
        retune_on_remesh=True, remesh_tune_budget_s=0.05
    )
    detach_remesh_hook(eng)
    x = jnp.ones((4, 2), jnp.float32)
    eng.offload(eng.make_descriptor("SCAN", p=4, payload_bytes=8), x)
    notify_remesh((4, 2), (2, 2))
    assert eng.cache_size() == 1  # untouched
