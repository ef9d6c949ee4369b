"""Compile the main path's kernels and schedules for a described TPU v5e.

No chip is attached: ``jax.experimental.topologies`` describes a ``v5e:2x2``
host and the TPU compiler, which is installed, compiles for it. That catches
what Pallas interpret mode cannot — tiles Mosaic refuses, scoped VMEM
overruns — at real sizes: the scan kernels at Mamba2-130M's shapes, the
prefix scan with its custom-VJP backward pass, the fused collective kernel
in both forms up to the largest payload the VMEM budget admits (the spmd
form compiles although ``supports_plan`` refuses it on a chip), the
op-per-round ``lower_spmd`` schedules, and the engine's stacked (sim-mode)
schedules at 64 MiB per rank.

The topology is described in a module fixture and never at import: one
process at a time may load the TPU library, and every xdist worker imports
this file. Code that asks ``jax.devices()`` still sees the CPU here, so a
fixture steers the kernels' platform checks to the chip, and the persistent
compile cache is off around these compiles (what they would write cannot be
read back without a chip).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.operators import get_operator
from repro.core.packet import CollType
from repro.kernels import ops, pallas_collective
from repro.offload.engine import OffloadEngine
from repro.offload.planner import (
    PhaseKind,
    PlanPhase,
    build_plan,
    lower_spmd,
)

MIB = 2**20
COLLS = ("SCAN", "EXSCAN", "ALLREDUCE", "BARRIER")


@pytest.fixture(scope="module")
def topo():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
        from jax.experimental import topologies

        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def chip(topo):
    """Steer the kernels to their native (non-interpret) lowering and keep
    the persistent compile cache out of the way."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()  # traces made for the CPU must not be reused
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pallas_collective, "on_tpu", lambda: True)
        mp.setattr(ops, "_use_pallas", lambda force: (True, False))
        yield topo
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(chip):
    return SingleDeviceSharding(chip.devices[0])


@pytest.fixture(scope="module")
def mesh(chip):
    return Mesh(np.array(chip.devices), ("i",))


def _hlo(fn, *args, **jit_kw) -> str:
    return jax.jit(fn, **jit_kw).lower(*args).compile().as_text()


def _op(coll):
    return get_operator("max" if coll == "BARRIER" else "sum")


def _fits(plan, axis_names=None) -> bool:
    """Does ``plan`` pass the fused backend's size gate? The sim form asks
    ``supports_plan``; the spmd form, which ``supports_plan`` refuses on a
    TPU (``spmd_unverified``), is held to the same VMEM budget."""
    if axis_names is None:
        return pallas_collective.supports_plan(plan)[0]
    return (
        pallas_collective.kernel_vmem_bytes(plan, axis_names)
        <= pallas_collective.vmem_limit_bytes()
    )


def _spmd_form(plan):
    """The spmd form lowered natively, past the chip refusal: it must
    still compile for the chip."""
    return pallas_collective._lower_pallas_spmd(
        plan, get_operator(plan.op_name), ("i",), interpret=False
    )


def _largest_admitted(coll, p, axis_names=None) -> int:
    """Largest per-rank payload, in whole 32 KiB blocks, the fused backend
    admits for ``coll`` over ``p`` ranks."""
    step = 32 * 1024
    ok = lambda n: _fits(  # noqa: E731
        build_plan(coll, (p,), _op(coll), n * step), axis_names
    )
    lo, hi = 1, 2**15  # 32 KiB .. 1 GiB
    assert ok(lo) and not ok(hi)
    while hi - lo > 1:
        mid = (lo + hi) // 2
        lo, hi = (mid, hi) if ok(mid) else (lo, mid)
    return lo * step


# ------------------------------------------------------------ scan kernels
# Mamba2-130M at batch 4 x seq 2048, chunk 256: the SSD mixer's within-chunk
# decay scan runs over (batch, chunks, heads, chunk) = (4, 8, 24, 256); the
# recurrence kernel over (batch, time, d_inner) = (4, 2048, 1536).


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "bwd"])
def test_prefix_scan_compiles(one_chip, backward):
    x = jax.ShapeDtypeStruct((4, 8, 24, 256), jnp.float32, sharding=one_chip)
    fn = ops.prefix_scan
    if backward:
        fn = jax.grad(lambda v: jnp.sum(jnp.sin(ops.prefix_scan(v))))
    assert "tpu_custom_call" in _hlo(fn, x)


def test_ssd_scan_compiles(one_chip):
    a = jax.ShapeDtypeStruct((4, 2048, 1536), jnp.float32, sharding=one_chip)
    fn = lambda u, v: ops.ssd_scan(u, v)[0]  # noqa: E731
    assert "tpu_custom_call" in _hlo(fn, a, a)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8, jnp.int32])
def test_prefix_scan_narrow_and_integer_dtypes_compile(one_chip, dtype):
    x = jax.ShapeDtypeStruct((64, 1024), dtype, sharding=one_chip)
    assert "tpu_custom_call" in _hlo(
        lambda v: ops.prefix_scan(v, op="max"), x
    )


# -------------------------------------------------- fused collective kernel


def _sizes(coll, p, axis_names=None):
    return (4, MIB, _largest_admitted(coll, p, axis_names))


@pytest.mark.parametrize("coll", COLLS)
def test_fused_sim_form_compiles(one_chip, coll):
    """Stacked (8, ...) form, one chip, at 4 B, 1 MiB and the largest
    payload the VMEM budget admits."""
    p = 8
    for nbytes in _sizes(coll, p):
        plan = build_plan(coll, (p,), _op(coll), nbytes)
        fn = pallas_collective.lower_pallas(plan)
        if coll == "BARRIER":
            text = _hlo(lambda: fn(None), out_shardings=one_chip)
        else:
            x = jax.ShapeDtypeStruct(
                (p, nbytes // 4), jnp.float32, sharding=one_chip
            )
            text = _hlo(fn, x)
        assert "tpu_custom_call" in text, (coll, nbytes)


def _spmd(fn, mesh):
    def body(xs):
        return jnp.asarray(fn(xs[0]))[None]

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P("i"),), out_specs=P("i"),
        check_vma=False,
    )


@pytest.mark.parametrize("coll", COLLS)
def test_fused_spmd_form_compiles(mesh, coll):
    """Per-rank form under shard_map on the 4-chip mesh, remote DMAs."""
    p = 4
    for nbytes in _sizes(coll, p, ("i",)):
        plan = build_plan(coll, (p,), _op(coll), nbytes)
        fn = _spmd_form(plan)
        x = jax.ShapeDtypeStruct(
            (p, max(1, nbytes // 4)), jnp.float32,
            sharding=NamedSharding(mesh, P("i")),
        )
        assert "tpu_custom_call" in _hlo(_spmd(fn, mesh), x), (coll, nbytes)


@pytest.mark.parametrize("inclusive", [True, False], ids=["inc", "exc"])
def test_fused_scan_total_compiles(one_chip, mesh, inclusive):
    """The two-stream FUSED_SCAN_TOTAL phase, both forms, at 1 MiB."""
    op = get_operator("sum")
    phase = PlanPhase(
        PhaseKind.FUSED_SCAN_TOTAL, 0, "fused_doubling",
        inclusive=inclusive, src=("x",), dst="y", dst2="t",
    )
    for p, axis_names in ((8, None), (4, ("i",))):
        base = build_plan("SCAN" if inclusive else "EXSCAN", (p,), op, MIB)
        plan = dataclasses.replace(base, phases=(phase,), result="t")
        if axis_names is None:
            fn = pallas_collective.lower_pallas(plan)
            x = jax.ShapeDtypeStruct(
                (p, MIB // 4), jnp.float32, sharding=one_chip
            )
            text = _hlo(fn, x)
        else:
            x = jax.ShapeDtypeStruct(
                (p, MIB // 4), jnp.float32,
                sharding=NamedSharding(mesh, P("i")),
            )
            text = _hlo(_spmd(_spmd_form(plan), mesh), x)
        assert "tpu_custom_call" in text, axis_names


@pytest.mark.parametrize("axis_names", [None, ("i",)], ids=["sim", "spmd"])
def test_vmem_budget_refuses_the_next_block(chip, axis_names):
    p = 8 if axis_names is None else 4
    top = _largest_admitted("SCAN", p, axis_names)
    plan = build_plan("SCAN", (p,), get_operator("sum"), top + 32 * 1024)
    assert not _fits(plan, axis_names)
    if axis_names is None:
        assert pallas_collective.supports_plan(plan) == (False, "vmem")


def test_spmd_form_refused_on_the_chip(chip):
    plan = build_plan("SCAN", (4,), get_operator("sum"), MIB)
    assert pallas_collective.supports_plan(plan, ("i",)) == (
        False, "spmd_unverified"
    )


# ------------------------------------------------- op-per-round schedules


@pytest.mark.parametrize("coll", ["SCAN", "EXSCAN"])
def test_lower_spmd_compiles(mesh, coll):
    op = get_operator("sum")
    fn = lower_spmd(build_plan(coll, (4,), op, MIB), ("i",), op)
    x = jax.ShapeDtypeStruct(
        (4, MIB // 4), jnp.float32, sharding=NamedSharding(mesh, P("i"))
    )
    assert "collective-permute" in _hlo(_spmd(fn, mesh), x)


def _entry_ops(text: str) -> list:
    """Opcodes of the compiled module's entry computation."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[: entry.index("\n}")]
    return re.findall(r"=\s*\S+\s+([a-z][\w-]*)\(", entry)


@pytest.mark.parametrize("coll", ["SCAN", "EXSCAN", "ALLREDUCE"])
def test_sim_schedule_has_no_row_updates(one_chip, coll):
    """The stacked schedule at 64 MiB per rank, p=8: every permute is one
    fused pass over the (8, 2**24) stack, never a chain of per-rank
    dynamic-update-slices (the rank axis lies inside the stack's tiles, so
    each would rewrite all 512 MiB), and a SCAN's shifts copy no slice."""
    fn = OffloadEngine._build_sim(
        CollType[coll], get_operator("sum"), "hillis_steele", 8, 0
    )
    x = jax.ShapeDtypeStruct((8, 2**24), jnp.int32, sharding=one_chip)
    ops_ = _entry_ops(_hlo(fn, x))
    assert "fusion" in ops_, ops_
    assert "dynamic-update-slice" not in ops_, ops_
    if coll == "SCAN":
        assert "slice" not in ops_, ops_
