"""Lowering-backend registry tests: the contract, soft capability
fallback (with engine telemetry), cache-key stability for the mode
defaults, the tuner's backend column / measured backend winners feeding
``make_descriptor(backend="auto")``, the folded-in hierarchical entry
points, and the fused-Pallas-kernel bitwise gate vs ``lower_spmd``
(subprocess, multi-device)."""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SSD, SUM
from repro.core.operators import get_operator
from repro.core.selector import set_active_tuning
from repro.kernels import pallas_collective
from repro.offload import OffloadEngine, TuningCache, backends
from repro.offload.passes import choose_backend
from repro.offload.planner import build_plan, lower_sim

P = 8
N = 16


@pytest.fixture(autouse=True)
def _no_active_tuning():
    set_active_tuning(None)
    yield
    set_active_tuning(None)


def _payload(seed=0, p=P):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-5, 6, size=(p, N)).astype(np.float32))


# ------------------------------------------------------------------ registry


def test_registry_names_and_fingerprints():
    assert backends.backend_names() == ("pallas", "sim", "spmd")
    # the mode defaults MUST contribute no cache-key fields (key stability)
    assert backends.get_backend("sim").fingerprint() == ()
    assert backends.get_backend("spmd").fingerprint() == ()
    assert backends.get_backend("pallas").fingerprint() == (
        ("backend", "pallas"),
    )
    assert backends.default_backend_name(None) == "sim"
    assert backends.default_backend_name(("i",)) == "spmd"


def test_unknown_and_default_backend_names_raise():
    with pytest.raises(ValueError, match="unknown lowering backend"):
        backends.get_backend("netfpga")
    # "" is mode-dependent; only resolve() may map it
    with pytest.raises(ValueError, match="mode-dependent"):
        backends.get_backend("")


def test_resolve_soft_fallback_reasons():
    single = build_plan("SCAN", (P,), SUM, 4 * N)
    multi = build_plan("SCAN", (2, 4), SUM, 4 * N)

    # in-capability request resolves to the named backend, no reason
    b, reason = backends.resolve("pallas", single)
    assert b.name == "pallas" and reason == ""

    # default name resolves to the mode default, never counted
    b, reason = backends.resolve("", single)
    assert b.name == "sim" and reason == ""
    b, reason = backends.resolve("", multi, ("a", "b"))
    assert b.name == "spmd" and reason == ""

    # capability misses fall back with the stable telemetry token
    b, reason = backends.resolve("pallas", multi, ("a", "b"))
    assert b.name == "spmd" and reason == "multi_axis_mesh"
    b, reason = backends.resolve("pallas", multi)
    assert b.name == "sim" and reason == "not_single_axis"
    chunked = dataclasses.replace(single, chunking=4)
    b, reason = backends.resolve("pallas", chunked)
    assert b.name == "sim" and reason == "chunked"

    # a typo is a bug, not a capability miss
    with pytest.raises(ValueError, match="unknown lowering backend"):
        backends.resolve("netfpga", single)


@pytest.mark.parametrize("opname", ["max", "ssd"])
def test_non_zero_identity_ops_rejected(opname):
    """The kernel's zero-fill recv IS its identity handling, so operators
    whose identity is not all-zeros are outside the capability envelope."""
    op = SSD if opname == "ssd" else get_operator(opname)
    plan = build_plan("SCAN", (P,), op, 4 * N)
    ok, reason = pallas_collective.supports_plan(plan, ("i",))
    assert not ok and reason == "op_flags"
    b, reason = backends.resolve("pallas", plan)
    assert b.name == "sim" and reason == "op_flags"


def test_plan_without_payload_size_refused():
    """The VMEM gate prices the declared payload: a plan that declares none
    cannot be priced, so it is refused rather than admitted."""
    plan = dataclasses.replace(build_plan("SCAN", (P,), SUM, 4 * N),
                               payload_bytes=0)
    assert pallas_collective.supports_plan(plan) == (False, "payload_unknown")


def test_multi_leaf_payload_over_vmem_raises_before_compile():
    """At the largest admitted one-leaf payload (4 MiB/rank at p=8, the
    whole budget), the same bytes split over two leaves fit, but twice them
    do not: the kernel checks the tiles it builds and raises instead of
    handing Mosaic a kernel over its VMEM limit."""
    mib4 = 4 * 2**20
    plan = build_plan("SCAN", (P,), SUM, mib4)
    assert pallas_collective.supports_plan(plan) == (True, "")
    fn = backends.get_backend("pallas").lower(plan, SUM)
    leaf = lambda n: jax.ShapeDtypeStruct((P, n), jnp.float32)  # noqa: E731
    jax.eval_shape(fn, {"a": leaf(mib4 // 8), "b": leaf(mib4 // 8)})
    with pytest.raises(ValueError, match="bytes of VMEM for 2 packed"):
        jax.eval_shape(fn, {"a": leaf(mib4 // 4), "b": leaf(mib4 // 4)})


# ------------------------------------------------------- sim-form bitwise


@pytest.mark.parametrize("coll", ["SCAN", "EXSCAN"])
def test_sim_form_bitwise_vs_lower_sim(coll):
    """The fused kernel's stacked-input form matches the op-per-round sim
    lowering bit for bit (interpret mode, no mesh)."""
    plan = build_plan(coll, (P,), SUM, 4 * N)
    x = _payload()
    ref = lower_sim(plan, SUM)(x)
    got = backends.get_backend("pallas").lower(plan, SUM)(x)
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- engine dispatch + cache


def test_engine_pinned_pallas_bitwise_distinct_cache_entry():
    eng = OffloadEngine()
    x = _payload()
    default = eng.make_descriptor(
        "SCAN", axes=(1, P), payload_bytes=4 * N, backend=""
    )
    pinned = eng.make_descriptor(
        "SCAN", axes=(1, P), payload_bytes=4 * N, backend="pallas"
    )
    ref = np.asarray(eng.offload(default, x))
    got = np.asarray(eng.offload(pinned, x))
    np.testing.assert_array_equal(ref, got)
    # the pallas fingerprint gives the fused schedule its own cache row;
    # no fallback was taken (the plan is in-capability)
    assert eng.cache_size() == 2
    t = eng.telemetry.snapshot()
    assert t["backend_fallbacks"] == 0
    assert t["backend_fallback_reasons"] == {}


def test_engine_fallback_shares_cache_entry_and_counts_once():
    eng = OffloadEngine()
    x = _payload()
    default = eng.make_descriptor(
        "SCAN", axes=(2, 4), payload_bytes=4 * N, backend=""
    )
    pinned = eng.make_descriptor(
        "SCAN", axes=(2, 4), payload_bytes=4 * N, backend="pallas"
    )
    ref = np.asarray(eng.offload(default, x))
    got = np.asarray(eng.offload(pinned, x))
    np.testing.assert_array_equal(ref, got)
    # the fallen-back dispatch resolves to the default lowering with the
    # default (empty) fingerprint -> it reuses the default's cache entry
    assert eng.cache_size() == 1
    t = eng.telemetry.snapshot()
    assert t["backend_fallbacks"] == 1
    assert t["backend_fallback_reasons"] == {"not_single_axis": 1}
    # repeat dispatch: memoized resolution, no double counting
    np.asarray(eng.offload(pinned, x))
    assert eng.telemetry.snapshot()["backend_fallbacks"] == 1


def test_default_backend_cache_key_is_stable():
    """A descriptor that doesn't name a backend produces the same single
    cache entry whether built before or after the registry existed — the
    default's empty fingerprint adds no key fields."""
    eng = OffloadEngine()
    x = _payload()
    auto = eng.make_descriptor("SCAN", axes=(1, P), payload_bytes=4 * N)
    assert auto.backend == ""  # untuned "auto" resolves to the default
    eng.offload(auto, x)
    keys_before = set(eng._cache)
    explicit = eng.make_descriptor(
        "SCAN", axes=(1, P), payload_bytes=4 * N, backend=""
    )
    eng.offload(explicit, x)
    assert set(eng._cache) == keys_before
    assert eng.cache_size() == 1


# ------------------------------------------------ tuning: backend winners


def _cache_with_race(default_s, pallas_s, payload=1024):
    cache = TuningCache(backend="test")
    cache.record_schedule(
        "scan", (1, P), True, 1, payload, default_s, backend=""
    )
    cache.record_schedule(
        "scan", (1, P), True, 1, payload, pallas_s, backend="pallas"
    )
    return cache


def test_backend_winners_reduce_and_tie_toward_default():
    cache = _cache_with_race(2e-5, 1e-5)
    assert cache.backend_winners == {("scan", (1, P), 1024): "pallas"}
    # nearest-payload lookup, exact sizes only
    assert cache.backend_winner("scan", (1, P), 2048) == "pallas"
    assert cache.backend_winner("scan", (2, 4), 1024) is None
    # ties break toward "" (the reference semantics)
    tied = _cache_with_race(1e-5, 1e-5)
    assert tied.backend_winners == {("scan", (1, P), 1024): ""}
    # a grid point with only default rows never steers backend="auto"
    solo = TuningCache(backend="test")
    solo.record_schedule("scan", (1, P), True, 1, 1024, 1e-5, backend="")
    assert solo.backend_winners == {}
    assert solo.backend_winner("scan", (1, P), 1024) is None


def test_schedule_winners_ignore_non_default_backend_rows():
    """The (optimized, chunks) schedule winner compares like with like:
    only default-backend rows compete, however fast the pallas row was."""
    cache = TuningCache(backend="test")
    cache.record_schedule("scan", (1, P), False, 1, 1024, 3e-5, backend="")
    cache.record_schedule("scan", (1, P), True, 1, 1024, 2e-5, backend="")
    cache.record_schedule(
        "scan", (1, P), False, 1, 1024, 1e-6, backend="pallas"
    )
    assert cache.schedule_winners[("scan", (1, P), 1024)] == (True, 1)


def test_backend_column_json_round_trip(tmp_path):
    import json

    cache = _cache_with_race(2e-5, 1e-5)
    back = TuningCache.load(cache.save(tmp_path / "tt.json"))
    assert sorted(m.backend for m in back.fusion_measurements) == [
        "", "pallas",
    ]
    assert back.backend_winners == cache.backend_winners
    # rows from tables written before the backend column default to ""
    d = cache.to_json()
    for row in d["fusion_measurements"]:
        row.pop("backend", None)
    legacy_path = tmp_path / "legacy.json"
    legacy_path.write_text(json.dumps(d))
    legacy = TuningCache.load(legacy_path)
    assert all(m.backend == "" for m in legacy.fusion_measurements)
    assert legacy.backend_winners == {}


def test_choose_backend_and_descriptor_auto_resolution():
    # untuned: the mode default, never speculative
    assert choose_backend("scan", (1, P), 1024) == ""
    eng = OffloadEngine()
    desc = eng.make_descriptor("SCAN", axes=(1, P), payload_bytes=1024)
    assert desc.backend == ""

    set_active_tuning(_cache_with_race(2e-5, 1e-5))
    assert choose_backend("scan", (1, P), 1024) == "pallas"
    assert choose_backend("scan", (2, 4), 1024) == ""  # no race recorded
    tuned = eng.make_descriptor("SCAN", axes=(1, P), payload_bytes=1024)
    assert tuned.backend == "pallas"
    # the winner travels on the wire and still dispatches bitwise-equal
    # (capability-checked at compile time like any pinned backend)
    x = _payload()
    got = np.asarray(eng.offload(tuned.encode(), x))
    set_active_tuning(None)
    ref = np.asarray(eng.offload(desc, x))
    np.testing.assert_array_equal(ref, got)


# --------------------------------------- hierarchical entry points folded in


def test_hierarchical_module_folded_into_backends():
    with pytest.raises(ModuleNotFoundError):
        import repro.offload.hierarchical  # noqa: F401


def test_sim_hierarchical_scan_matches_flat_reference():
    rng = np.random.default_rng(3)
    stacked = jnp.asarray(
        rng.integers(-5, 6, size=(2, 4, N)).astype(np.float32)
    )
    out = backends.sim_hierarchical_scan(stacked, "sum", 2, 4)
    want = np.cumsum(
        np.asarray(stacked).reshape(8, N), axis=0
    ).reshape(2, 4, N)
    np.testing.assert_array_equal(np.asarray(out), want)


# --------------------------------------------- spmd bitwise gate (subprocess)


def test_pallas_check_spmd_bitwise(subprocess_runner):
    """lower_pallas == lower_spmd bit-for-bit on a 1x8 host mesh:
    SCAN/EXSCAN (sum), BARRIER, both FUSED_SCAN_TOTAL forms, plus the
    op_flags capability rejections."""
    out = subprocess_runner("repro.testing.pallas_check", str(P))
    assert f"pallas_check,scan:sum,p,{P},bitwise,1" in out
    assert f"pallas_check,barrier,p,{P},bitwise,1" in out
