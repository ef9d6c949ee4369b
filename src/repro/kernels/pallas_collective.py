"""Fused Pallas "NIC" kernels: one kernel per schedule, not one op per round.

The source paper's thesis is that MPI_Scan wins when the whole collective
runs inside the network device. ``lower_sim``/``lower_spmd`` are the
host-driven op-per-round baseline; this module is the offloaded analogue —
each communication phase of a :class:`~repro.offload.planner.CollectivePlan`
lowers to a *single* Pallas kernel that executes every exchange round
internally with RDMA-style ``make_async_remote_copy`` sends and per-slot DMA
semaphore waits between rounds (the NIC-triggered-operation model of the
Quadrics barrier and sPIN handler papers in PAPERS.md).

Two forms share the same round structure:

* **spmd form** (``axis_names`` given): a per-rank kernel run under
  ``shard_map`` over ONE named mesh axis. Each exchange posts a remote copy
  of the accumulator into a receive slot of its own on the partner (a slot
  is written once per kernel, so a fast sender never overwrites data its
  receiver has not read), blocks on that slot's DMA semaphores, masks the
  cyclic wrap back to ``ppermute``'s zero-fill, and folds with the exact
  operand order of the op-per-round schedule — outputs are bitwise
  identical to ``lower_spmd``.
* **sim form** (no ``axis_names``): the single-device rehearsal over stacked
  ``(p, ...)`` leaves; the same rounds run as local ``make_async_copy``
  shifts of rank slabs through two alternating receive slots. This is the
  variant the autotuner races against the op-per-round interpreter and the
  engine's sim mode dispatches.

Payloads stay in HBM; each kernel copies them into VMEM tiles laid out for
Mosaic (see "Tile layout" below) and copies the results back out. The whole
payload is resident in VMEM, so :func:`supports_plan` refuses any plan whose
tiles exceed the scoped VMEM the kernels ask for.

Where no TPU is attached the kernels run in Pallas interpret mode, which
fully discharges the DMAs — CI exercises the real send/wait structure on
CPU. On a TPU they never do. The interpreter's remote-DMA discharge requires
a *scalar* logical ``device_id``, exactly one named mesh axis in scope, and
rounds that are full permutations; the kernels honor all three (cyclic
sends + receiver masking reproduce the partial-permutation zero-fill), and
plans outside the supported set (multi-axis under shard_map, chunked C > 1,
non-doubling scan algorithms, non-pow2 butterflies, operators that are not
leafwise, payloads over the VMEM budget, and the spmd form on a TPU, whose
remote DMAs have run only in interpret mode) are reported by
:func:`supports_plan` so the lowering registry can fall back to the
op-per-round default.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import algorithms as alg
from repro.core.operators import MAX, AssocOp, get_operator
from repro.core.packet import CollType
from repro.core.reduce_ops import allreduce_schedule, reduce_schedule
from repro.core.scan_collective import sim_scan
from repro.offload.planner import (
    CollectivePlan,
    PhaseKind,
    _along_axis,
    _zero_coord_mask,
)

PyTree = Any

#: phase kinds the fused kernels implement on the active (size > 1) level
_COMM_KINDS = (
    PhaseKind.SCAN,
    PhaseKind.FUSED_SCAN_TOTAL,
    PhaseKind.TOTAL,
    PhaseKind.BARRIER,
)


def on_tpu() -> bool:
    """Is the default device a TPU? Device errors propagate: a failed query
    must not quietly select interpret mode."""
    return jax.devices()[0].platform == "tpu"


def active_level(plan: CollectivePlan) -> Optional[int]:
    """The single logical level with size > 1, or None if the plan is not
    effectively single-axis (zero or several non-trivial levels)."""
    active = [lv for lv, s in enumerate(plan.logical_sizes) if s > 1]
    return active[0] if len(active) == 1 else None


def supports_plan(
    plan: CollectivePlan, axis_names: Optional[Sequence[str]] = None
) -> Tuple[bool, str]:
    """Can the fused-kernel backend lower ``plan``? Returns ``(ok, reason)``
    with a stable reason token for telemetry when it can't.

    Supported: effectively single-axis plans (one logical level of size
    > 1; size-1 levels run the identical local shortcuts), whole payloads
    (chunking == 1), hillis-steele SCAN / FUSED_SCAN_TOTAL over a
    zero-identity operator, and pow2 TOTAL/BARRIER butterflies, over a
    leafwise operator (the tile layout flattens leaves), with tiles that fit
    the kernels' scoped VMEM (:func:`kernel_vmem_bytes` against
    :func:`vmem_limit_bytes`; reason ``vmem``) — a plan that does not say
    its payload size (``payload_bytes == 0``) is refused as
    ``payload_unknown``. The spmd form additionally requires exactly one
    named mesh axis (the interpret remote-DMA discharge supports no more),
    and is refused on a TPU (``spmd_unverified``): it compiles for the
    chip, but its remote DMAs have run only in interpret mode, and no
    engine entry point reaches it — planned descriptors span two or more
    mesh axes, refused as ``multi_axis_mesh``.
    """
    if plan.chunking > 1:
        return False, "chunked"
    if axis_names is not None and (
        len(axis_names) != 1 or len(plan.sizes) != 1
    ):
        return False, "multi_axis_mesh"
    if axis_names is not None and on_tpu():
        return False, "spmd_unverified"
    if plan.payload_bytes <= 0:
        return False, "payload_unknown"
    lv = active_level(plan)
    if lv is None:
        return False, "not_single_axis"
    p = plan.logical_sizes[lv]
    op = get_operator(plan.op_name)
    for ph in plan.phases:
        if ph.kind in (PhaseKind.COMBINE, PhaseKind.IDENTITY):
            continue
        if ph.level != lv:
            continue  # size-1 level: local shortcut, no kernel needed
        if ph.kind == PhaseKind.SCAN:
            if ph.algorithm != "hillis_steele":
                return False, f"algorithm:{ph.algorithm}"
            if not op.zero_identity:
                return False, "op_flags"
        elif ph.kind == PhaseKind.FUSED_SCAN_TOTAL:
            if not op.zero_identity:
                return False, "op_flags"
        elif ph.kind in (PhaseKind.TOTAL, PhaseKind.BARRIER):
            if p & (p - 1):
                return False, "non_pow2_butterfly"
        else:
            return False, f"phase:{ph.kind.name.lower()}"
        if ph.kind != PhaseKind.BARRIER and op.name not in _LEAFWISE_OPS:
            return False, "op_layout"
    if kernel_vmem_bytes(plan, axis_names) > vmem_limit_bytes():
        return False, "vmem"
    return True, ""


def kernel_round_structure(
    plan: CollectivePlan,
) -> Tuple[Tuple[str, int], ...]:
    """``(phase_kind_name, rounds)`` per fused comm phase, in plan order —
    the round structure the kernels execute internally, consumed by
    :func:`repro.obs.tracing.add_kernel_round_spans`."""
    lv = active_level(plan)
    out = []
    if lv is None:
        return ()
    p = plan.logical_sizes[lv]
    for ph in plan.phases:
        if ph.kind in _COMM_KINDS and ph.level == lv:
            out.append(
                (
                    ph.kind.name,
                    alg.phase_round_count(
                        ph.kind.name, p, inclusive=ph.inclusive
                    ),
                )
            )
    return tuple(out)


# ---------------------------------------------------------------------------
# Tile layout shared by both forms
# ---------------------------------------------------------------------------
#
# Mosaic DMAs and loads only whole (sublane, lane) tiles. So each rank's
# share of a leaf is flattened and zero-padded to a ``(rows, 128)`` tile,
# ``rows`` a multiple of the dtype's sublane count; the sim form stacks
# ranks on a leading, untiled axis ``(p, rows, 128)``, where rank slices
# are free. Combines run over blocks of ``_BLOCK_ROWS`` rows so the values
# the kernel holds stay a few vregs, whatever the payload.

_LANES = 128
_BLOCK_ROWS = 64
#: operators that combine each leaf elementwise: the only ones whose result
#: survives flattening and padding the leaves into tiles
_LEAFWISE_OPS = frozenset({"sum", "prod", "max", "min"})


def _sublanes(dtype) -> int:
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _tile_rows(n: int, dtype) -> int:
    """Rows of the ``(rows, 128)`` tile holding ``n`` elements: sublane
    aligned, and a whole number of combine blocks when it spans several."""
    rows = -(-max(int(n), 1) // _LANES)
    step = _BLOCK_ROWS if rows > _BLOCK_ROWS else _sublanes(dtype)
    return -(-rows // step) * step


def _pack(leaf: jax.Array, stacked: bool) -> jax.Array:
    """``(...) -> (rows, 128)``, or ``(p, ...) -> (p, rows, 128)``."""
    lead = leaf.shape[:1] if stacked else ()
    flat = leaf.reshape(lead + (-1,))
    n = flat.shape[-1]
    rows = _tile_rows(n, leaf.dtype)
    pad = [(0, 0)] * len(lead) + [(0, rows * _LANES - n)]
    return jnp.pad(flat, pad).reshape(lead + (rows, _LANES))


def _unpack(tile: jax.Array, like_shape, stacked: bool) -> jax.Array:
    lead = like_shape[:1] if stacked else ()
    n = math.prod(like_shape[len(lead):])
    return tile.reshape(lead + (-1,))[..., :n].reshape(like_shape)


def _for_blocks(rows: int, fn) -> None:
    """Call ``fn(block)`` for every ``pl.ds`` row block of a tile."""
    br = min(rows, _BLOCK_ROWS)
    if rows == br:
        fn(pl.ds(0, br))
        return

    def body(i, carry):
        fn(pl.ds(pl.multiple_of(i * br, br), br))
        return carry

    lax.fori_loop(0, rows // br, body, 0)


def vmem_limit_bytes() -> int:
    """Scoped VMEM the fused kernels ask for: 3/4 of one TensorCore's VMEM
    on the attached TPU. Off the chip the kernels run in interpret mode and
    are sized for the TPU v5e (128 MiB), so a plan is admitted or refused
    alike on both."""
    capacity = 128 * 2**20
    if jax.devices()[0].platform == "tpu":
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    return capacity * 3 // 4


def _recv_slots(p: int) -> int:
    """Receive slots per stream in the spmd form: one per exchange (a
    stream makes at most ``num_steps(p) + 1``), so no send can overwrite a
    slot its receiver has not read yet."""
    return alg.num_steps(p) + 1


def _vmem_bytes(tile_bytes: int, streams: int, slots: int) -> int:
    """VMEM a comm kernel holds for ``tile_bytes`` of packed tiles (all
    leaves, all stacked ranks): per stream an accumulator plus ``slots``
    receive slots. Payload in and out stay in HBM."""
    return streams * (1 + slots) * tile_bytes


def kernel_vmem_bytes(
    plan: CollectivePlan, axis_names: Optional[Sequence[str]] = None
) -> int:
    """VMEM one fused comm kernel of ``plan`` holds when its per-rank
    payload (``plan.payload_bytes``, priced as 32-bit words) is one leaf:
    receive slots are two in the sim form, :func:`_recv_slots` in the spmd
    form, and the sim form stacks ``p`` ranks. A payload split over several
    leaves pads each leaf to its own tile and can need more; the kernel
    checks the tiles it actually builds (:func:`_launch`)."""
    lv = active_level(plan)
    p = plan.logical_sizes[lv] if lv is not None else 1
    tile = _tile_rows(-(-plan.payload_bytes // 4), jnp.float32) * _LANES * 4
    streams = (
        2
        if any(ph.kind == PhaseKind.FUSED_SCAN_TOTAL for ph in plan.phases)
        else 1
    )
    if axis_names is None:
        return _vmem_bytes(plan.p * tile, streams, 2)
    return _vmem_bytes(tile, streams, _recv_slots(p))


def _launch(
    body, leaves, treedef, tiles, streams, slots, semaphores, *,
    stacked, interpret,
):
    """Run a comm-phase kernel over packed tiles: payloads in and results
    out stay in HBM (``pl.ANY``); VMEM holds one accumulator and ``slots``
    receive slots per leaf and stream. Returns the result tree, or the
    ``(scan, total)`` pair of trees for two streams. Raises ``ValueError``
    when those tiles exceed :func:`vmem_limit_bytes` (a payload of many
    small leaves can, although its plan was admitted)."""
    need = _vmem_bytes(
        sum(t.size * t.dtype.itemsize for t in tiles), streams, slots
    )
    if need > vmem_limit_bytes():
        raise ValueError(
            f"fused collective kernel needs {need} bytes of VMEM for "
            f"{len(tiles)} packed leaves, over its {vmem_limit_bytes()}-byte "
            "limit; use the registry default lowering"
        )
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    outs = pl.pallas_call(
        body,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles]
        * streams,
        in_specs=[any_spec] * len(tiles),
        out_specs=[any_spec] * (len(tiles) * streams),
        scratch_shapes=(
            [pltpu.VMEM(t.shape, t.dtype) for t in tiles] * streams
            + [pltpu.VMEM((slots,) + t.shape, t.dtype) for t in tiles]
            * streams
            + semaphores
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes()
        ),
        interpret=interpret,
    )(*tiles)
    L = len(leaves)
    trees = [
        jax.tree.unflatten(
            treedef,
            [
                _unpack(o, leaf.shape, stacked)
                for o, leaf in zip(outs[i * L : (i + 1) * L], leaves)
            ],
        )
        for i in range(streams)
    ]
    return trees[0] if streams == 1 else tuple(trees)


# ---------------------------------------------------------------------------
# spmd form: per-rank kernels under shard_map, remote DMA rounds
# ---------------------------------------------------------------------------
#
# Every round is issued as a *full* permutation (cyclic shift by +/-d, or the
# XOR butterfly) so each rank receives exactly one incoming copy per round —
# the invariant the interpret discharge rule needs — and the receiver masks
# wrapped rows back to zero, reproducing the op-per-round ``ppermute``
# zero-fill bit for bit.


def _spmd_comm_kernel(
    kind: PhaseKind,
    p: int,
    axis_name: str,
    op: AssocOp,
    *,
    inclusive: bool = True,
    interpret: bool = True,
):
    """Build ``fn(tree) -> tree`` (or ``(tree, tree)`` for FUSED_SCAN_TOTAL)
    running one whole comm phase as a single per-rank Pallas kernel."""
    nsteps = alg.num_steps(p)
    fused = kind == PhaseKind.FUSED_SCAN_TOTAL
    streams = 2 if fused else 1
    nslots = _recv_slots(p)

    def run(x: PyTree):
        leaves, treedef = jax.tree.flatten(x)
        tiles = [_pack(l, stacked=False) for l in leaves]
        L = len(tiles)
        ns = L * streams

        def body(*refs):
            ins, outs = refs[:L], refs[L : L + ns]
            acc = refs[L + ns : L + 2 * ns]
            recv = refs[L + 2 * ns : L + 3 * ns]
            copy_sem, send_sem, recv_sem = refs[L + 3 * ns :]
            rank = lax.axis_index(axis_name)
            steps = [0] * streams

            def local_copies(pairs):
                copies = [
                    pltpu.make_async_copy(src, dst, copy_sem.at[i])
                    for i, (src, dst) in enumerate(pairs)
                ]
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()

            def exchange(dst_rank, stream):
                """Remote-copy one stream's accumulators into the partner's
                next receive slot; returns ``(copies, slot)``. Read the slot
                only after the copies' ``wait``."""
                slot = steps[stream]
                steps[stream] += 1
                copies = [
                    pltpu.make_async_remote_copy(
                        src_ref=acc[si],
                        dst_ref=recv[si].at[slot],
                        send_sem=send_sem.at[slot, si],
                        recv_sem=recv_sem.at[slot, si],
                        device_id=dst_rank,
                        device_id_type=pltpu.DeviceIdType.LOGICAL,
                    )
                    for si in range(stream * L, (stream + 1) * L)
                ]
                return copies, slot

            def run_round(*copies):
                """Issue all of a round's DMAs before waiting on any (full
                duplex)."""
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()

            def fold(update):
                """``update(li, blk)`` over every leaf's row blocks."""
                for li in range(L):
                    _for_blocks(
                        tiles[li].shape[0], lambda blk, li=li: update(li, blk)
                    )

            def masked(v, keep):
                return jnp.where(keep, v, jnp.zeros_like(v))

            local_copies([(ins[li], acc[li]) for li in range(L)])
            if kind in (PhaseKind.TOTAL, PhaseKind.BARRIER):
                # pow2 recursive-doubling butterfly; the XOR rounds are full
                # permutations so the flag stream of allreduce_schedule is
                # constantly 1 and _combine_lr reduces to a plain combine.
                for k in range(nsteps):
                    d = 1 << k
                    copies, slot = exchange(rank ^ d, 0)
                    run_round(*copies)
                    partner_lower = (rank & d) != 0

                    def butterfly(li, blk, slot=slot, lower=partner_lower):
                        rv = recv[li][slot, blk, :]
                        mine = acc[li][blk, :]
                        acc[li][blk, :] = jnp.where(
                            lower, op.combine(rv, mine), op.combine(mine, rv)
                        )

                    fold(butterfly)
                local_copies([(acc[li], outs[li]) for li in range(L)])
                return

            # doubling scans: stream 0 = prefix, stream 1 = suffix (fused)
            if not inclusive:
                # structural entry shift: rank r starts from x_{r-1}
                copies, slot = exchange(lax.rem(rank + 1, p), 0)
                run_round(*copies)

                def entry(li, blk, slot=slot):
                    acc[li][blk, :] = masked(recv[li][slot, blk, :], rank >= 1)

                fold(entry)
            if fused:
                local_copies([(ins[li], acc[L + li]) for li in range(L)])
            for k in range(nsteps):
                d = 1 << k
                pre, pre_slot = exchange(lax.rem(rank + d, p), 0)
                suf, suf_slot = [], None
                if fused:
                    suf, suf_slot = exchange(lax.rem(rank - d + p, p), 1)
                # full duplex: both directions' sends in flight at once
                run_round(*pre, *suf)

                def scan_round(li, blk, d=d, pre_slot=pre_slot,
                               suf_slot=suf_slot):
                    acc[li][blk, :] = op.combine(
                        masked(recv[li][pre_slot, blk, :], rank >= d),
                        acc[li][blk, :],
                    )
                    if fused:
                        si = L + li
                        acc[si][blk, :] = op.combine(
                            acc[si][blk, :],
                            masked(recv[si][suf_slot, blk, :], rank < p - d),
                        )

                fold(scan_round)
            if fused:
                # exits (same arithmetic as alg.scan_total_schedule); the
                # total lands in the suffix accumulator
                if inclusive:
                    copies, slot = exchange(lax.rem(rank - 1 + p, p), 1)
                    run_round(*copies)

                    def exit_(li, blk, slot=slot):
                        acc[L + li][blk, :] = op.combine(
                            acc[li][blk, :],
                            masked(recv[L + li][slot, blk, :], rank < p - 1),
                        )
                else:

                    def exit_(li, blk):
                        y = acc[li][blk, :]
                        acc[L + li][blk, :] = op.combine(y, acc[L + li][blk, :])
                        acc[li][blk, :] = masked(y, rank != 0)

                fold(exit_)
            local_copies([(acc[si], outs[si]) for si in range(ns)])

        return _launch(
            body, leaves, treedef, tiles, streams, nslots,
            [
                pltpu.SemaphoreType.DMA((ns,)),
                pltpu.SemaphoreType.DMA((nslots, ns)),
                pltpu.SemaphoreType.DMA((nslots, ns)),
            ],
            stacked=False, interpret=interpret,
        )

    return run


# ---------------------------------------------------------------------------
# sim form: whole-mesh (p, ...) kernels, local DMA rank-slab shifts
# ---------------------------------------------------------------------------


def _sim_comm_kernel(
    kind: PhaseKind,
    p: int,
    op: AssocOp,
    *,
    inclusive: bool = True,
    interpret: bool = True,
):
    """Build the single-device form over stacked ``(p, ...)`` leaves: the
    same rounds as the spmd kernel, realized as local ``make_async_copy``
    shifts of rank slabs (rank r's tile moves to rank r+d); ranks with no
    sender combine zeros, exactly like ``ppermute``'s zero-fill. Ranks are
    static here, so the masks are too."""
    nsteps = alg.num_steps(p)
    fused = kind == PhaseKind.FUSED_SCAN_TOTAL
    streams = 2 if fused else 1

    def run(x: PyTree):
        leaves, treedef = jax.tree.flatten(x)
        tiles = [_pack(l, stacked=True) for l in leaves]
        L = len(tiles)
        ns = L * streams

        def body(*refs):
            ins, outs = refs[:L], refs[L : L + ns]
            acc = refs[L + ns : L + 2 * ns]
            recv = refs[L + 2 * ns : L + 3 * ns]
            sem = refs[-1]
            step = 0

            def copy_all(pairs):
                copies = [
                    pltpu.make_async_copy(src, dst, sem.at[i])
                    for i, (src, dst) in enumerate(pairs)
                ]
                for c in copies:
                    c.start()
                for c in copies:
                    c.wait()

            def shift(d, stream):
                """One round: every rank's tile moves by ``d`` (+d = toward
                higher ranks) into this round's recv slot; returns the slot.
                Rows with no sender are left unwritten."""
                nonlocal step
                slot = step & 1
                step += 1
                lo, n = (0, p - d) if d > 0 else (-d, p + d)
                copy_all(
                    (acc[si].at[pl.ds(lo, n)],
                     recv[si].at[slot, pl.ds(lo + d, n)])
                    for si in range(stream * L, (stream + 1) * L)
                )
                return slot

            def bfly(d):
                """XOR-partner round as 2*(p / 2d) slab swaps (full perm)."""
                nonlocal step
                slot = step & 1
                step += 1
                copy_all(
                    (acc[li].at[pl.ds(a, d)], recv[li].at[slot, pl.ds(b, d)])
                    for li in range(L)
                    for base in range(0, p, 2 * d)
                    for a, b in ((base, base + d), (base + d, base))
                )
                return slot

            def fold(update):
                """``update(li, r, blk)`` over every leaf, rank and block."""
                for li in range(L):

                    def blocks(blk, li=li):
                        for r in range(p):
                            update(li, r, blk)

                    _for_blocks(tiles[li].shape[1], blocks)

            def recv_or_zero(si, slot, r, blk, has_sender):
                v = recv[si][slot, r, blk, :]
                return v if has_sender else jnp.zeros_like(v)

            copy_all((ins[li], acc[li]) for li in range(L))
            if kind in (PhaseKind.TOTAL, PhaseKind.BARRIER):
                for k in range(nsteps):
                    d = 1 << k
                    slot = bfly(d)

                    def butterfly(li, r, blk, d=d, slot=slot):
                        rv = recv[li][slot, r, blk, :]
                        mine = acc[li][r, blk, :]
                        acc[li][r, blk, :] = (
                            op.combine(rv, mine)
                            if r & d
                            else op.combine(mine, rv)
                        )

                    fold(butterfly)
                copy_all((acc[li], outs[li]) for li in range(L))
                return

            if not inclusive:
                slot = shift(1, 0)

                def entry(li, r, blk, slot=slot):
                    acc[li][r, blk, :] = recv_or_zero(li, slot, r, blk, r >= 1)

                fold(entry)
            if fused:
                copy_all((ins[li], acc[L + li]) for li in range(L))
            for k in range(nsteps):
                d = 1 << k
                pre_slot = shift(d, 0)
                suf_slot = shift(-d, 1) if fused else None

                def scan_round(li, r, blk, d=d, pre_slot=pre_slot,
                               suf_slot=suf_slot):
                    acc[li][r, blk, :] = op.combine(
                        recv_or_zero(li, pre_slot, r, blk, r >= d),
                        acc[li][r, blk, :],
                    )
                    if fused:
                        si = L + li
                        acc[si][r, blk, :] = op.combine(
                            acc[si][r, blk, :],
                            recv_or_zero(si, suf_slot, r, blk, r < p - d),
                        )

                fold(scan_round)
            if fused:
                if inclusive:
                    slot = shift(-1, 1)

                    def exit_(li, r, blk, slot=slot):
                        acc[L + li][r, blk, :] = op.combine(
                            acc[li][r, blk, :],
                            recv_or_zero(L + li, slot, r, blk, r < p - 1),
                        )
                else:

                    def exit_(li, r, blk):
                        y = acc[li][r, blk, :]
                        acc[L + li][r, blk, :] = op.combine(
                            y, acc[L + li][r, blk, :]
                        )
                        if r == 0:
                            acc[li][r, blk, :] = jnp.zeros_like(y)

                fold(exit_)
            copy_all((acc[si], outs[si]) for si in range(ns))

        return _launch(
            body, leaves, treedef, tiles, streams, 2,
            [pltpu.SemaphoreType.DMA((L * max(p, streams),))],
            stacked=True, interpret=interpret,
        )

    return run


# ---------------------------------------------------------------------------
# Plan lowering: the same phase loop as lower_sim/lower_spmd, with every
# comm phase on the active level replaced by one fused kernel
# ---------------------------------------------------------------------------


def _sim_fallback_fn(ph, op, chunks_backend):
    """The op-per-round functions lower_sim uses — only reached for size-1
    levels, where they are communication-free local shortcuts."""
    if ph.kind == PhaseKind.SCAN:
        return lambda t: sim_scan(
            t, op, chunks_backend.p, algorithm=ph.algorithm,
            inclusive=ph.inclusive, backend=chunks_backend,
        )
    if ph.kind == PhaseKind.FUSED_SCAN_TOTAL:
        return lambda t: alg.scan_total_schedule(
            chunks_backend, t, op, inclusive=ph.inclusive
        )
    if ph.kind == PhaseKind.TOTAL:
        return lambda t: allreduce_schedule(
            chunks_backend, t, op, algorithm=ph.algorithm
        )
    if ph.kind == PhaseKind.REDUCE:
        return lambda t: reduce_schedule(
            chunks_backend, t, op, root=ph.root, algorithm=ph.algorithm
        )
    if ph.kind == PhaseKind.BARRIER:
        return lambda t: allreduce_schedule(
            chunks_backend, t, MAX, algorithm=ph.algorithm
        )
    raise ValueError(f"unknown phase kind {ph.kind!r}")


def _lower_pallas_sim(
    plan: CollectivePlan, op: AssocOp, interpret: bool, traced: bool
):
    logical = plan.logical_sizes
    k = len(logical)
    p_total = plan.p
    lv_active = active_level(plan)
    coll_name = plan.coll.name.lower()

    def to_mesh(tree: PyTree) -> PyTree:
        return jax.tree.map(lambda a: a.reshape(logical + a.shape[1:]), tree)

    def to_flat(tree: PyTree) -> PyTree:
        return jax.tree.map(
            lambda a: a.reshape((p_total,) + a.shape[k:]), tree
        )

    def run(x: Optional[PyTree]) -> PyTree:
        regs = {}
        if plan.coll == CollType.BARRIER:
            regs["x"] = jnp.ones(logical, jnp.float32)
        else:
            regs["x"] = to_mesh(x)
        tracer = None
        if traced:
            from repro.obs import tracing as obs_tracing

            tracer = obs_tracing.get_tracer()
        for ph in plan.phases:
            if ph.kind == PhaseKind.COMBINE:
                merged = op.combine(regs[ph.src[0]], regs[ph.src[1]])
                if ph.guard_levels:
                    mask = _zero_coord_mask(logical, ph.guard_levels)
                    merged = alg._bwhere(mask, regs[ph.src[1]], merged)
                regs[ph.dst] = merged
                continue
            if ph.kind == PhaseKind.IDENTITY:
                regs[ph.dst] = op.identity_like(regs[ph.src[0]])
                continue
            p_axis = logical[ph.level]
            phase_op = MAX if ph.kind == PhaseKind.BARRIER else op
            if ph.level == lv_active and ph.kind in _COMM_KINDS:
                fn = _sim_comm_kernel(
                    ph.kind, p_axis, phase_op,
                    inclusive=ph.inclusive, interpret=interpret,
                )
                rounds = alg.phase_round_count(
                    ph.kind.name, p_axis, inclusive=ph.inclusive
                )
            else:
                fn = _sim_fallback_fn(ph, op, alg.SimBackend(p_axis))
                rounds = 0
            if tracer is not None and rounds:
                from repro.obs import tracing as obs_tracing

                t0 = obs_tracing.now_us()
                out = jax.block_until_ready(
                    _along_axis(regs[ph.src[0]], ph.level, fn)
                )
                obs_tracing.add_kernel_round_spans(
                    tracer,
                    phase=f"{ph.kind.name}:L{ph.level}",
                    coll=coll_name,
                    rounds=rounds,
                    start_us=t0,
                    end_us=obs_tracing.now_us(),
                )
            else:
                out = _along_axis(regs[ph.src[0]], ph.level, fn)
            if ph.kind == PhaseKind.FUSED_SCAN_TOTAL:
                regs[ph.dst], regs[ph.dst2] = out
            else:
                regs[ph.dst] = out
        return to_flat(regs[plan.result])

    return run


def _lower_pallas_spmd(
    plan: CollectivePlan,
    op: AssocOp,
    axis_names: Sequence[str],
    interpret: bool,
):
    name = axis_names[plan.order[0]]
    p = plan.sizes[0]

    def run(x: Optional[PyTree] = None) -> PyTree:
        regs = {}
        if plan.coll == CollType.BARRIER:
            regs["x"] = jnp.ones((), jnp.float32)
        else:
            regs["x"] = x
        for ph in plan.phases:
            if ph.kind == PhaseKind.COMBINE:
                merged = op.combine(regs[ph.src[0]], regs[ph.src[1]])
                if ph.guard_levels:
                    keep = jnp.asarray(True)
                    for lv in ph.guard_levels:
                        keep = keep & (lax.axis_index(name) == 0)
                    merged = alg._bwhere(keep, regs[ph.src[1]], merged)
                regs[ph.dst] = merged
                continue
            if ph.kind == PhaseKind.IDENTITY:
                regs[ph.dst] = op.identity_like(regs[ph.src[0]])
                continue
            phase_op = MAX if ph.kind == PhaseKind.BARRIER else op
            fn = _spmd_comm_kernel(
                ph.kind, p, name, phase_op,
                inclusive=ph.inclusive, interpret=interpret,
            )
            out = fn(regs[ph.src[0]])
            if ph.kind == PhaseKind.FUSED_SCAN_TOTAL:
                regs[ph.dst], regs[ph.dst2] = out
            else:
                regs[ph.dst] = out
        return regs[plan.result]

    return run


def lower_pallas(
    plan: CollectivePlan,
    op: "AssocOp | str | None" = None,
    *,
    axis_names: Optional[Sequence[str]] = None,
    traced: bool = False,
):
    """Compile a supported plan to fused-Pallas-kernel schedules.

    Mirrors the :func:`repro.offload.planner.lower_sim` /
    :func:`~repro.offload.planner.lower_spmd` calling conventions — with
    ``axis_names`` the result runs per-rank inside ``shard_map`` over one
    named axis; without, it runs over flat stacked ``(p, ...)`` leaves on a
    single device. Outputs are bitwise identical to the op-per-round
    lowerings (same arithmetic, same operand order, same zero-fills).

    The kernels run natively on a TPU and in Pallas interpret mode
    anywhere else, so CI exercises the DMA structure on CPU. Raises
    ``ValueError`` for plans outside :func:`supports_plan`; callers wanting
    a soft fallback go through the lowering registry
    (``repro.offload.backends``).
    """
    op = get_operator(plan.op_name if op is None else op)
    ok, reason = supports_plan(plan, axis_names)
    if not ok:
        raise ValueError(
            f"plan not supported by the pallas backend ({reason}); "
            f"use the registry default lowering"
        )
    inter = not on_tpu()
    if axis_names is None:
        return _lower_pallas_sim(plan, op, inter, traced)
    return _lower_pallas_spmd(plan, op, axis_names, inter)
