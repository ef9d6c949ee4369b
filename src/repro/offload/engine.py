"""The offload engine: one descriptor in, one result out.

This is the software analogue of the paper's NIC firmware loop. The NetFPGA
accepted a single self-describing packet (Fig. 1) and ran the whole collective
in hardware; here :class:`OffloadEngine` accepts a
:class:`~repro.core.packet.CollectiveDescriptor` (or its encoded uint32 word
vector straight off the wire), compiles the described schedule once, caches it
keyed by the descriptor words, and dispatches every subsequent identical
request straight from the cache — with hit/miss/latency telemetry standing in
for the paper's 8 ns on-NIC timer.

Three execution modes, mirroring the repo's backends:

  * **sim** (``axis_name=None``): payloads are stacked ``(p, ...)`` arrays on
    one device; the engine owns the dispatch, jits the fused schedule, and
    measures wall-clock latency per offload.
  * **spmd** (``axis_name="..."``): called from *inside* ``shard_map``; the
    cached schedule closure is inlined into the caller's trace (the compiled
    XLA program is the "NIC"), so the engine counts hits/misses but leaves
    timing to the profiler.
  * **driver** (``axis_name=...`` plus ``mesh=...``): called from *outside*
    any trace. The engine wraps the schedule in its own
    ``jit(shard_map(...))`` over the given mesh, compiles it once per
    descriptor, and dispatches the compiled program on every offload — the
    closest software analogue of the paper's host/NIC split: the host
    computes locally, rings the doorbell with a descriptor, and the
    pre-programmed engine runs the collective. Payload layout is the sim
    contract (stacked ``(p, ...)`` leaves, leading axis in the plan's
    *logical* rank order); sharding in/out follows the descriptor's split,
    so repeat dispatches move no data. Latency is wall-clock, like sim.

All five descriptor CollTypes dispatch through the same path: SCAN, EXSCAN,
REDUCE, ALLREDUCE, BARRIER. Descriptors carrying a multi-axis topology
(``axes`` + ``split``) compile through the collective planner
(:mod:`repro.offload.planner`): the plan's phase list is derived from the
descriptor, run through the plan-optimizer pass pipeline when the
descriptor's ``optimized`` flag is set (:mod:`repro.offload.passes` —
SCAN+TOTAL fusion, dead-phase elimination, permute threading), lowered
through the same sim/spmd backend pair, and cached under a fingerprint of
the *optimized plan* rather than the raw words — descriptors whose plans
converge after the passes (different ``comm_id``; ``(2,4)`` split ``(1,0)``
vs ``(4,2)`` split ``(0,1)``; size-1 axes pruned) share one compiled
schedule, so the optimizer shrinks compile count as well as round count. In
spmd mode ``axis_name`` is a tuple naming the physical mesh axes in
descriptor order. :meth:`OffloadEngine.profile_offload` additionally runs
one dispatch under ``jax.profiler`` and feeds the device-side schedule time
back into the telemetry (``device_latency_by_coll_us``), the
measured-on-device latency source for driver/SPMD modes.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algorithms as alg
from repro.core.operators import AssocOp, get_operator
from repro.core.packet import (
    CollType,
    CollectiveDescriptor,
    MsgType,
    WireDType,
    WireOp,
)
from repro.core.reduce_ops import (
    allreduce_schedule,
    barrier_schedule,
    reduce_schedule,
)
from repro.core.scan_collective import dist_exscan, dist_scan, sim_scan
from repro.core.selector import select_algorithm
from repro.obs import events as obs_events
from repro.obs import metrics as obs_metrics
from repro.obs import tracing as obs_tracing
from repro.offload import planner

PyTree = Any
AxisSpec = Union[str, Sequence[str], None]

#: the coll kind each CollType tunes/selects against (the measured tables are
#: keyed by these names — never price a reduce with the scan table)
COLL_KIND = {
    CollType.SCAN: "scan",
    CollType.EXSCAN: "exscan",
    CollType.REDUCE: "reduce",
    CollType.ALLREDUCE: "allreduce",
    CollType.BARRIER: "barrier",
}

_WIRE_OP_NAMES = {
    WireOp.SUM: "sum",
    WireOp.PROD: "prod",
    WireOp.MAX: "max",
    WireOp.MIN: "min",
    WireOp.SSD: "ssd",
    WireOp.FLASH: "flash",
}
_WIRE_OP_IDS = {v: k for k, v in _WIRE_OP_NAMES.items()}

_WIRE_DTYPES = {
    WireDType.INT32: jnp.int32,
    WireDType.FLOAT32: jnp.float32,
    WireDType.BFLOAT16: jnp.bfloat16,
    WireDType.FLOAT16: jnp.float16,
    WireDType.INT8: jnp.int8,
}
_WIRE_DTYPE_IDS = {jnp.dtype(v): k for k, v in _WIRE_DTYPES.items()}


_chaos_mod = None


def _chaos_active() -> bool:
    """Whether a chaos-injector scope is installed.

    Lazy import: ``repro.runtime`` must not load at offload import time
    (its ``__init__`` pulls the trainer stack, which imports this
    package); after the first call this is a module-attribute read.
    """
    global _chaos_mod
    if _chaos_mod is None:
        from repro.runtime import chaos

        _chaos_mod = chaos
    return _chaos_mod.active()


def wire_op_name(op: WireOp) -> str:
    return _WIRE_OP_NAMES[WireOp(op)]


def wire_op_id(name: str) -> WireOp:
    try:
        return _WIRE_OP_IDS[name]
    except KeyError:
        raise ValueError(
            f"operator {name!r} has no wire id; known: {sorted(_WIRE_OP_IDS)}"
        ) from None


def wire_dtype(dt: WireDType):
    return _WIRE_DTYPES[WireDType(dt)]


@dataclasses.dataclass
class EngineTelemetry:
    """Counters the engine maintains per dispatch (the NIC status registers)."""

    hits: int = 0
    misses: int = 0
    dispatches: int = 0
    compiles: int = 0
    errors: int = 0
    calls_by_coll: Dict[str, int] = dataclasses.field(default_factory=dict)
    total_latency_s: float = 0.0
    last_latency_s: float = 0.0
    timed_dispatches: int = 0
    cache_size: int = 0
    cache_clears: int = 0
    latency_by_coll: Dict[str, Tuple[float, int]] = dataclasses.field(
        default_factory=dict
    )
    device_latency_by_coll: Dict[str, Tuple[float, int]] = dataclasses.field(
        default_factory=dict
    )
    latency_source_by_coll: Dict[str, str] = dataclasses.field(
        default_factory=dict
    )
    profiler_fallbacks: int = 0
    profiler_fallback_reasons: Dict[str, int] = dataclasses.field(
        default_factory=dict
    )
    backend_fallbacks: int = 0
    backend_fallback_reasons: Dict[str, int] = dataclasses.field(
        default_factory=dict
    )

    def record_dispatch(self, coll: str, latency_s: Optional[float]) -> None:
        self.dispatches += 1
        self.calls_by_coll[coll] = self.calls_by_coll.get(coll, 0) + 1
        reg = obs_metrics.get_registry()
        reg.counter(
            "repro_engine_dispatches_total",
            "engine offload dispatches",
            labelnames=("coll",),
        ).inc(coll=coll)
        if latency_s is not None:
            self.timed_dispatches += 1
            self.total_latency_s += latency_s
            self.last_latency_s = latency_s
            tot, n = self.latency_by_coll.get(coll, (0.0, 0))
            self.latency_by_coll[coll] = (tot + latency_s, n + 1)
            self.latency_source_by_coll.setdefault(coll, "wall")
            reg.histogram(
                "repro_engine_dispatch_latency_us",
                "wall-clock latency of timed engine dispatches",
                labelnames=("coll",),
            ).observe(latency_s * 1e6, coll=coll)

    def record_device_latency(
        self, coll: str, latency_s: float, *, source: str = "profiler"
    ) -> None:
        """A per-schedule device timing from a profiler trace (or, when the
        trace could not be parsed, the wall fallback — labeled as such).
        This is the measured-on-device source behind ``latency_by_coll_us``:
        the wall numbers include dispatch/transfer/sync, the profiler
        numbers are the collective itself. The accumulated mean is never
        mixed-source: the first trace-derived sample evicts any wall
        fallbacks, and wall fallbacks never dilute a profiler-labeled mean.
        """
        prior = self.latency_source_by_coll.get(coll)
        if source == "profiler":
            if prior != "profiler":
                self.device_latency_by_coll.pop(coll, None)
            self.latency_source_by_coll[coll] = "profiler"
        elif prior == "profiler":
            return  # keep the device-only mean; drop the wall sample
        elif prior is None:
            self.latency_source_by_coll[coll] = source
        tot, n = self.device_latency_by_coll.get(coll, (0.0, 0))
        self.device_latency_by_coll[coll] = (tot + latency_s, n + 1)
        if source == "profiler":
            obs_metrics.get_registry().histogram(
                "repro_engine_device_latency_us",
                "profiler-derived device-side schedule latency",
                labelnames=("coll",),
            ).observe(latency_s * 1e6, coll=coll)

    def record_profiler_fallback(self, coll: str, reason: str) -> None:
        """A ``profile_offload`` run degraded to ``source="wall"`` — count
        it and the why, so dashboards can alert on profiler degradation
        instead of quietly trusting wall numbers."""
        self.profiler_fallbacks += 1
        self.profiler_fallback_reasons[reason] = (
            self.profiler_fallback_reasons.get(reason, 0) + 1
        )
        obs_metrics.get_registry().counter(
            "repro_engine_profiler_fallbacks_total",
            "profile_offload runs that fell back to wall-clock timing",
            labelnames=("coll", "reason"),
        ).inc(coll=coll, reason=reason)
        obs_events.record("profiler_fallback", coll=coll, reason=reason)

    def record_backend_fallback(self, coll: str, reason: str) -> None:
        """A descriptor named a lowering backend whose capability check
        missed for its plan, and the dispatch fell back to the registry
        default. Counted once per unique (descriptor, axis-binding)
        resolution, not per dispatch, mirroring the memoized resolution."""
        self.backend_fallbacks += 1
        self.backend_fallback_reasons[reason] = (
            self.backend_fallback_reasons.get(reason, 0) + 1
        )
        obs_metrics.get_registry().counter(
            "repro_engine_backend_fallbacks_total",
            "lowering-backend requests that fell back to the default",
            labelnames=("coll", "reason"),
        ).inc(coll=coll, reason=reason)
        obs_events.record("backend_fallback", coll=coll, reason=reason)

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def mean_latency_s(self) -> float:
        return (
            self.total_latency_s / self.timed_dispatches
            if self.timed_dispatches
            else 0.0
        )

    def snapshot(self) -> Dict[str, Any]:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": self.hit_rate,
            "dispatches": self.dispatches,
            "compiles": self.compiles,
            "errors": self.errors,
            "cache_size": self.cache_size,
            "cache_clears": self.cache_clears,
            "calls_by_coll": dict(self.calls_by_coll),
            "mean_latency_us": self.mean_latency_s * 1e6,
            "last_latency_us": self.last_latency_s * 1e6,
            "latency_by_coll_us": {
                coll: (tot / n) * 1e6 if n else 0.0
                for coll, (tot, n) in self.latency_by_coll.items()
            },
            "device_latency_by_coll_us": {
                coll: (tot / n) * 1e6 if n else 0.0
                for coll, (tot, n) in self.device_latency_by_coll.items()
            },
            "latency_source_by_coll": dict(self.latency_source_by_coll),
            "profiler_fallbacks": self.profiler_fallbacks,
            "profiler_fallback_reasons": dict(self.profiler_fallback_reasons),
            "backend_fallbacks": self.backend_fallbacks,
            "backend_fallback_reasons": dict(self.backend_fallback_reasons),
        }


@dataclasses.dataclass(frozen=True)
class CompiledSchedule:
    """A cache entry: the closure that runs one descriptor's collective."""

    key: bytes
    coll: str
    algo: str
    op_name: str
    p: int
    fn: Callable[[PyTree], PyTree]


class OffloadEngine:
    """Descriptor-driven collective dispatch with a compiled-schedule cache.

    The cache key is the encoded descriptor word vector with the per-rank
    fields (rank, msg_type) normalized away — every rank of a communicator,
    and every repeat offload, shares one compiled schedule, which is exactly
    the "program the NIC once, stream requests" contract of the paper.
    """

    def __init__(self) -> None:
        self._cache: Dict[bytes, CompiledSchedule] = {}
        # planned descriptors cache-key on the *optimized plan*, not the
        # raw words: requests whose plans converge after the pass pipeline
        # (different comm_id; (2,4) split (1,0) vs (4,2) split (0,1); size-1
        # axes pruned away) share one compiled schedule, so fusion also
        # shrinks compile count. _plan_memo maps normalized words -> plan;
        # _fp_memo memoizes the plan fingerprint per (words, axis names)
        # so a repeat dispatch is a dict lookup, not a rehash of the phase
        # list; _plans stashes the plan under the final key for _compile.
        self._plan_memo: Dict[bytes, Any] = {}
        self._fp_memo: Dict[Tuple[bytes, Any], bytes] = {}
        self._plans: Dict[bytes, Any] = {}
        # memoized lowering-backend resolution per (requested name, plan,
        # axis binding): repeat dispatches neither re-run the capability
        # check nor re-count a fallback in telemetry
        self._backend_memo: Dict[Tuple[str, Any, Any], Tuple] = {}
        self.telemetry = EngineTelemetry()

    # -- descriptor helpers ------------------------------------------------

    @staticmethod
    def _as_descriptor(
        descriptor: "CollectiveDescriptor | np.ndarray",
    ) -> CollectiveDescriptor:
        if isinstance(descriptor, CollectiveDescriptor):
            return descriptor
        return CollectiveDescriptor.decode(np.asarray(descriptor))

    @staticmethod
    def _mode_tag(axis_name: AxisSpec, mesh: Any = None) -> str:
        if axis_name is None:
            mode = "<sim>"
        elif isinstance(axis_name, str):
            mode = axis_name
        else:
            mode = "|".join(axis_name)
        if mesh is not None:
            shape = ",".join(
                f"{n}={s}" for n, s in zip(mesh.axis_names, mesh.devices.shape)
            )
            # device identity matters: two same-shape meshes over different
            # (or reordered) devices must not share a compiled program
            devs = hashlib.blake2s(
                ",".join(
                    str(getattr(d, "id", d)) for d in mesh.devices.flat
                ).encode("utf-8")
            ).hexdigest()[:12]
            mode = f"driver[{shape}@{devs}]|{mode}"
        return mode

    @classmethod
    def _cache_key(
        cls, desc: CollectiveDescriptor, axis_name: AxisSpec, mesh: Any = None
    ) -> bytes:
        normalized = desc.normalized()
        mode = cls._mode_tag(axis_name, mesh)
        return normalized.encode().tobytes() + b"|" + mode.encode("utf-8")

    def _plan_for(self, desc: CollectiveDescriptor):
        """The (optimized, when flagged) plan a multi-axis descriptor names
        plus its normalized wire words, memoized on those words."""
        words = desc.normalized().encode().tobytes()
        plan = self._plan_memo.get(words)
        if plan is None:
            itemsize = jnp.dtype(wire_dtype(desc.data_type)).itemsize
            payload_bytes = max(1, int(desc.count)) * itemsize
            plan = planner.build_plan(
                desc.coll_type,
                desc.axes,
                get_operator(wire_op_name(desc.operation)),
                payload_bytes,
                order=desc.split,
                root=int(desc.root),
            )
            if desc.optimized:
                from repro.offload import passes

                plan = passes.optimize_plan(plan)
            if desc.chunks > 1:
                # the descriptor's chunk word is authoritative — resolved
                # at make_descriptor time (winner table or cost model), it
                # must not be re-derived here or brokered/cached dispatches
                # could disagree on the compiled schedule's shape
                plan = dataclasses.replace(plan, chunking=int(desc.chunks))
            self._plan_memo[words] = plan
        return plan, words

    def _resolve_backend(
        self, desc: CollectiveDescriptor, plan, axis_name: AxisSpec
    ) -> Tuple[str, Tuple]:
        """Resolve the descriptor's lowering-backend request through the
        registry for this plan + axis binding; returns ``(name,
        fingerprint_fields)``. Soft capability misses fall back to the mode
        default and are counted in telemetry exactly once per unique
        resolution (the memo doubles as the dedup set)."""
        names = None
        if axis_name is not None:
            names = (
                (axis_name,)
                if isinstance(axis_name, str)
                else tuple(axis_name)
            )
        memo_key = (desc.backend, plan, names)
        cached = self._backend_memo.get(memo_key)
        if cached is None:
            from repro.offload import backends

            backend, reason = backends.resolve(desc.backend, plan, names)
            if reason:
                self.telemetry.record_backend_fallback(
                    desc.coll_type.name.lower(), reason
                )
            cached = (backend.name, backend.fingerprint())
            self._backend_memo[memo_key] = cached
        return cached

    def _planned_cache_key(
        self,
        words: bytes,
        plan,
        axis_name: AxisSpec,
        mesh: Any = None,
        backend_fields: Tuple = (),
    ) -> bytes:
        """Key a planned request on everything its lowering reads — and
        nothing more. In sim mode that is the logical structure alone; in
        spmd/driver modes the physical axis names per logical level join
        the fingerprint (two plans with one logical shape but different
        splits bind levels to different named axes). The digest is a pure
        function of (plan, names), so repeat dispatches resolve it from
        ``_fp_memo`` without rehashing the phase list."""
        names_l: Optional[Tuple[str, ...]] = None
        if axis_name is not None:
            names = (
                (axis_name,)
                if isinstance(axis_name, str)
                else tuple(axis_name)
            )
            if len(names) == len(plan.sizes):
                names_l = tuple(names[i] for i in plan.order)
            else:  # malformed; let _compile raise with its clear error
                names_l = names
        digest = self._fp_memo.get((words, names_l, backend_fields))
        if digest is None:
            fields = (
                plan.coll.name,
                plan.op_name,
                plan.logical_sizes,
                plan.result,
                plan.optimized,
                names_l,
                tuple(
                    (
                        int(ph.kind), ph.level, ph.algorithm,
                        ph.inclusive, ph.root, ph.src, ph.dst, ph.dst2,
                        ph.guard_levels,
                    )
                    for ph in plan.phases
                ),
            )
            # chunked plans get an extra fingerprint field; C=1 keeps the
            # pre-chunking digest bit-for-bit (cache-key stability)
            if plan.chunking > 1:
                fields = fields + (("chunks", int(plan.chunking)),)
            # ditto the backend: the mode defaults contribute no fields
            # (fingerprint() is empty), so every pre-registry key survives
            fields = fields + backend_fields
            digest = hashlib.blake2s(repr(fields).encode("utf-8")).digest()
            self._fp_memo[(words, names_l, backend_fields)] = digest
        mode = self._mode_tag(axis_name, mesh)
        return b"plan|" + digest + b"|" + mode.encode("utf-8")

    def make_descriptor(
        self,
        coll: "CollType | str",
        *,
        p: Optional[int] = None,
        payload_bytes: int,
        op: "AssocOp | str" = "sum",
        algorithm: str = "auto",
        comm_id: int = 0,
        root: int = 0,
        data_type: WireDType = WireDType.FLOAT32,
        count: Optional[int] = None,
        axes: Optional[Sequence[int]] = None,
        split: "str | Sequence[int]" = "auto",
        optimize: "str | bool" = "auto",
        chunks: "str | int" = "auto",
        backend: str = "auto",
    ) -> CollectiveDescriptor:
        """Build an offload request, resolving ``algorithm="auto"`` through
        the (tuning-table-aware) selector — the host-side half of the paper's
        'intelligent selection'. Selection consults the cost table of the
        *requested* coll kind (scan/exscan/reduce/allreduce/barrier), never a
        stand-in.

        With ``axes`` (2-3 mesh-axis sizes, outermost first), the request is
        a planned hierarchical collective: ``split="auto"`` asks the planner
        for the tuned logical axis order, and the resolved ``algo_type``
        names the innermost intra-phase schedule (per-phase algorithms are
        re-derived from the plan at compile time). ``optimize`` controls the
        plan-optimizer pass pipeline (``repro.offload.passes``): ``"auto"``
        consults the measured fusion winner / cost model
        (:func:`~repro.offload.passes.choose_optimization`), True/False
        force it. The resolved flag is encoded on the wire (word 16) so
        brokered and cached dispatches agree on whether passes ran.
        ``chunks`` is the chunked-streaming chunk count: ``"auto"``
        resolves through the measured schedule winner / pipelined cost
        model (:func:`~repro.offload.passes.choose_schedule` when
        ``optimize`` is also auto, :func:`~repro.offload.passes.
        select_chunking` otherwise), an int forces it; the resolved count
        travels as the 17th wire word when > 1 (single-axis requests
        always run unchunked).
        ``backend`` names the lowering backend for planned requests:
        ``"auto"`` consults the autotuner's measured backend winner
        (:func:`~repro.offload.passes.choose_backend`, falling back to the
        mode default when untuned), an explicit registry name ("pallas")
        pins it — subject to the soft capability fallback at compile time.
        Single-axis requests always use the mode default (the descriptor
        rejects a named backend without a topology).
        """
        if isinstance(coll, str):
            coll = CollType[coll.upper()]
        op = get_operator(op)
        if axes is not None:
            axes = tuple(int(a) for a in axes)
            if p is None:
                p = int(np.prod(axes))
        if p is None:
            raise ValueError("either p or axes is required")
        order: "tuple[int, ...]" = ()
        optimized = False
        chunk_count = 1
        backend_name = "" if backend == "auto" else str(backend)
        if axes is not None and len(axes) > 1:
            from repro.offload import passes

            if backend == "auto":
                backend_name = passes.choose_backend(
                    coll, axes, payload_bytes, op
                )

            if optimize == "auto" and chunks == "auto":
                # one resolution for both schedule halves: the measured
                # schedule winner (when tuned) or the cost model decides
                # fusion and chunk count together
                optimized, chunk_count = passes.choose_schedule(
                    coll, axes, payload_bytes, op
                )
            else:
                if optimize == "auto":
                    optimized = passes.choose_optimization(
                        coll, axes, payload_bytes, op
                    )
                else:
                    optimized = bool(optimize)
                if chunks == "auto":
                    plan = planner.build_plan(
                        coll, axes, op, payload_bytes, optimize=optimized
                    )
                    chunk_count = (
                        plan.chunking
                        if optimized
                        else passes.select_chunking(
                            plan, payload_bytes
                        ).chunking
                    )
                else:
                    chunk_count = int(chunks)
            order = (
                planner.plan_axis_order(
                    coll, axes, payload_bytes, op, optimize=optimized
                )
                if split == "auto"
                else tuple(int(i) for i in split)
            )
            if algorithm == "auto":
                # the innermost intra phase's schedule, for the wire field
                inner_p = axes[order[-1]]
                algorithm = select_algorithm(
                    inner_p, payload_bytes, op, coll=COLL_KIND[coll]
                )
        else:
            if chunks != "auto" and int(chunks) > 1:
                raise ValueError(
                    "chunked streaming requires a multi-axis (planned) "
                    f"request; got chunks={chunks} without axes"
                )
            if algorithm == "auto":
                algorithm = select_algorithm(
                    p, payload_bytes, op, coll=COLL_KIND[coll]
                )
        itemsize = jnp.dtype(wire_dtype(data_type)).itemsize
        if count is None:
            count = max(1, payload_bytes // itemsize)
        elif count * itemsize != payload_bytes:
            # plan compilation re-derives the payload from count * itemsize;
            # a divergent explicit count would tune the phases for a
            # different payload than the split/algo_type were selected for
            raise ValueError(
                f"count={count} x {itemsize}B contradicts "
                f"payload_bytes={payload_bytes}"
            )
        return CollectiveDescriptor(
            comm_id=comm_id,
            comm_size=p,
            coll_type=coll,
            algo_type=algorithm,
            root=root,
            operation=wire_op_id(op.name),
            data_type=data_type,
            count=count,
            axes=axes if (axes is not None and len(axes) > 1) else (),
            split=order,
            optimized=optimized,
            chunks=chunk_count,
            backend=backend_name,
        )

    # -- dispatch ----------------------------------------------------------

    def offload(
        self,
        descriptor: "CollectiveDescriptor | np.ndarray",
        x: Optional[PyTree] = None,
        axis_name: AxisSpec = None,
        mesh: Any = None,
    ) -> PyTree:
        """Run the collective the descriptor describes; return its result.

        ``x`` is the per-rank contribution: a stacked ``(p, ...)`` pytree in
        sim and driver modes (leading axis in the plan's *logical* rank
        order), the local shard inside ``shard_map`` in spmd mode. BARRIER
        ignores ``x``. For a planned multi-axis descriptor, ``axis_name`` is
        the tuple of physical mesh-axis names in descriptor ``axes`` order.
        Passing ``mesh`` (with ``axis_name``) selects driver mode: the
        engine owns the ``jit(shard_map(...))`` program, compiled on first
        dispatch and streamed from the cache afterwards.

        When a collecting tracer is installed (:mod:`repro.obs.tracing`)
        the dispatch is wrapped in ``engine``-category spans, and planned
        *sim*-mode requests run the eager traced plan interpreter — cached
        under a separate key, so the jitted schedule the default path uses
        is untouched — emitting one span per plan phase and one per
        communication round. Driver/spmd dispatches only get the host-side
        spans around the dispatch: inside jit there is no per-round host
        work to measure. With the default no-op tracer this method's
        behavior (and the compiled schedule cache) is byte-for-byte the
        untraced path.
        """
        tracer = obs_tracing.get_tracer()
        if not tracer.enabled:
            return self._offload(descriptor, x, axis_name, mesh, None)
        with tracer.span("engine.offload", "engine") as span:
            return self._offload(descriptor, x, axis_name, mesh, span)

    def _offload(
        self,
        descriptor: "CollectiveDescriptor | np.ndarray",
        x: Optional[PyTree],
        axis_name: AxisSpec,
        mesh: Any,
        span: Any,
    ) -> PyTree:
        try:
            desc = self._as_descriptor(descriptor)
        except Exception:
            self.telemetry.errors += 1
            raise
        if axis_name is not None and not isinstance(axis_name, str):
            axis_name = tuple(axis_name) or None
        if mesh is not None and axis_name is None:
            raise ValueError("driver mode (mesh=...) requires axis_name")
        # planned sim requests run the eager traced interpreter under a
        # tracer; it lives under its own cache key so the default jitted
        # schedule is never evicted or shadowed
        traced = span is not None and axis_name is None and mesh is None
        if len(desc.axes) > 1:
            try:
                plan, words = self._plan_for(desc)
            except Exception:
                self.telemetry.errors += 1
                raise
            _, bfields = self._resolve_backend(desc, plan, axis_name)
            key = self._planned_cache_key(
                words, plan, axis_name, mesh, backend_fields=bfields
            )
            if not traced and axis_name is None and mesh is None \
                    and _chaos_active():
                # a chaos scope must see (and be able to fail) individual
                # messages, which jit would bake into the compiled program:
                # route the dispatch onto the same eager interpreter — and
                # the same cache key — the tracer uses
                traced = True
            if traced:
                key += b"|traced"
            self._plans.setdefault(key, plan)
        else:
            traced = False
            key = self._cache_key(desc, axis_name, mesh)
        if span is not None:
            span.set(
                coll=desc.coll_type.name.lower(),
                mode=self._mode_tag(axis_name, mesh),
                p=int(desc.comm_size),
                traced_plan=traced,
            )
        sched = self._cache.get(key)
        if sched is None:
            tracer = obs_tracing.get_tracer() if span is not None else None
            try:
                if span is not None:
                    with tracer.span(
                        "engine.compile", "engine",
                        coll=desc.coll_type.name.lower(),
                    ):
                        sched = self._compile(
                            desc, key, axis_name, mesh, traced=traced
                        )
                else:
                    sched = self._compile(
                        desc, key, axis_name, mesh, traced=traced
                    )
            except Exception:
                self.telemetry.errors += 1
                raise
            self._cache[key] = sched
            self.telemetry.misses += 1
            self.telemetry.compiles += 1
            self.telemetry.cache_size = len(self._cache)
            cache_state = "miss"
            if span is not None:
                span.set(cache="miss")
            obs_metrics.get_registry().counter(
                "repro_engine_cache_events_total",
                "compiled-schedule cache lookups",
                labelnames=("event",),
            ).inc(event="miss")
            obs_events.record(
                "cache_miss", coll=sched.coll, scope="schedule"
            )
        else:
            self.telemetry.hits += 1
            cache_state = "hit"
            if span is not None:
                span.set(cache="hit")
            obs_metrics.get_registry().counter(
                "repro_engine_cache_events_total",
                "compiled-schedule cache lookups",
                labelnames=("event",),
            ).inc(event="hit")

        timed = axis_name is None or mesh is not None
        if desc.coll_type == CollType.BARRIER:
            if mesh is not None and x is None:
                x = jnp.zeros((desc.comm_size,), jnp.float32)
        elif timed:
            self._validate_sim_payload(desc, x)

        if timed:
            t0 = time.perf_counter()
            out = sched.fn(x)
            out = jax.tree.map(lambda a: a.block_until_ready(), out)
            latency = time.perf_counter() - t0
        else:
            out = sched.fn(x)
            latency = None  # inside a trace: the profiler owns timing
        self.telemetry.record_dispatch(sched.coll, latency)
        obs_events.record(
            "dispatch",
            coll=sched.coll,
            cache=cache_state,
            latency_us=None if latency is None else round(latency * 1e6, 1),
        )
        return out

    def profile_offload(
        self,
        descriptor: "CollectiveDescriptor | np.ndarray",
        x: Optional[PyTree] = None,
        *,
        axis_name: AxisSpec = None,
        mesh: Any = None,
        warmup: int = 1,
        trace_dir: Optional[str] = None,
    ):
        """Dispatch once under a ``jax.profiler`` trace and record the
        device-side schedule time into the telemetry (the SPMD/driver-mode
        latency story: the engine counts hits/misses inside ``shard_map``
        and the profiler owns timing — this wires the profiler's numbers
        back in). Returns a :class:`repro.offload.profiling.DeviceTiming`.
        Pass ``trace_dir`` to keep the profiler trace on disk (e.g. for
        :func:`repro.obs.export.merge_device_trace`).
        """
        from repro.offload.profiling import profile_offload as _profile

        return _profile(
            self, descriptor, x, axis_name=axis_name, mesh=mesh,
            warmup=warmup, trace_dir=trace_dir,
        )

    def cache_size(self) -> int:
        return len(self._cache)

    def clear(self) -> None:
        # reset the gauge at clear time: a remesh-triggered clear must not
        # keep reporting the pre-clear size until the next dispatch. The
        # plan memos clear too: a retune can change the per-phase
        # algorithms (and the fused-vs-unfused choice) a plan compiles to.
        self._cache.clear()
        self._plan_memo.clear()
        self._fp_memo.clear()
        self._plans.clear()
        self._backend_memo.clear()
        self.telemetry.cache_size = 0
        self.telemetry.cache_clears += 1

    # -- internals ---------------------------------------------------------

    @staticmethod
    def _validate_sim_payload(desc: CollectiveDescriptor, x: PyTree) -> None:
        if x is None:
            raise ValueError(
                f"{desc.coll_type.name} offload requires a payload"
            )
        for leaf in jax.tree.leaves(x):
            if jnp.ndim(leaf) < 1 or leaf.shape[0] != desc.comm_size:
                raise ValueError(
                    "sim-mode payload leaves need a leading rank axis of "
                    f"comm_size={desc.comm_size}; got shape {jnp.shape(leaf)}"
                )

    def _compile(
        self,
        desc: CollectiveDescriptor,
        key: bytes,
        axis_name: AxisSpec,
        mesh: Any = None,
        *,
        traced: bool = False,
    ) -> CompiledSchedule:
        op = get_operator(wire_op_name(desc.operation))
        algo = desc.algo_type
        coll = desc.coll_type
        p = int(desc.comm_size)
        root = int(desc.root)
        if coll == CollType.REDUCE and not 0 <= root < p:
            raise ValueError(
                f"REDUCE root={root} out of range for comm_size={p}"
            )

        if len(desc.axes) > 1:
            fn, bname = self._build_planned(
                desc, op, axis_name, plan=self._plans.get(key),
                traced=traced,
            )
            algo = f"plan{desc.split}:{algo}"
            if desc.optimized:
                algo = f"opt:{algo}"
            if desc.chunks > 1:
                algo = f"chunk{desc.chunks}:{algo}"
            if bname is not None:
                # only non-default backends tag the schedule, so the algo
                # strings pre-registry callers assert on are unchanged
                algo = f"{bname}:{algo}"
            if traced:
                algo = f"traced:{algo}"
        elif axis_name is not None:
            one = axis_name
            if not isinstance(one, str):
                if len(one) != 1:
                    raise ValueError(
                        f"descriptor has no multi-axis topology; pass one "
                        f"mesh axis name, not {one!r}"
                    )
                (one,) = one
            fn = self._build_spmd(coll, op, algo, one, root)
        else:
            fn = jax.jit(self._build_sim(coll, op, algo, p, root))
        if mesh is not None:
            fn = self._build_driver(desc, fn, axis_name, mesh)
        return CompiledSchedule(
            key=key,
            coll=coll.name.lower(),
            algo=algo,
            op_name=op.name,
            p=p,
            fn=fn,
        )

    @staticmethod
    def _build_driver(
        desc: CollectiveDescriptor,
        inner: Callable[[PyTree], PyTree],
        axis_name: AxisSpec,
        mesh: Any,
    ) -> Callable[[PyTree], PyTree]:
        """Wrap a spmd schedule closure in the engine's own shard_map + jit.

        The payload is the sim-mode stacked ``(p, ...)`` contract with the
        leading axis in *logical* rank order; the in/out spec shards it
        across the physical axes in the descriptor split's logical order
        (see ``sharding.specs.plan_spec``), so the stacked global array and
        the per-rank shards line up with zero data movement.
        """
        from jax.sharding import PartitionSpec as P

        names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
        missing = [n for n in names if n not in mesh.axis_names]
        if missing:
            raise ValueError(
                f"axes {missing} not in mesh axes {mesh.axis_names}"
            )
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        expect = desc.axes if len(desc.axes) > 1 else (desc.comm_size,)
        for n, want in zip(names, expect):
            if int(sizes[n]) != int(want):
                raise ValueError(
                    f"descriptor axis size {want} != mesh axis "
                    f"{n!r} size {sizes[n]}"
                )
        if len(desc.axes) > 1:
            order = desc.split or tuple(range(len(desc.axes)))
            names_l = tuple(names[i] for i in order)
        else:
            names_l = names
        entry = names_l[0] if len(names_l) == 1 else names_l
        spec = P(entry)

        def body(xs: PyTree) -> PyTree:
            xs = jax.tree.map(lambda a: a[0], xs)
            out = inner(xs)
            return jax.tree.map(lambda a: jnp.asarray(a)[None], out)

        return jax.jit(
            jax.shard_map(
                body,
                mesh=mesh,
                in_specs=(spec,),
                out_specs=spec,
                check_vma=False,
            )
        )

    def _build_planned(
        self,
        desc: CollectiveDescriptor,
        op: AssocOp,
        axis_name: AxisSpec,
        plan,
        traced: bool = False,
    ) -> "Tuple[Callable[[PyTree], PyTree], Optional[str]]":
        """Lower a multi-axis descriptor through the lowering-backend
        registry; returns ``(fn, backend_tag)`` where the tag is the
        resolved backend's name for non-defaults and ``None`` when the mode
        default lowered the plan (the compiled algo string stays as-is).

        ``plan`` is the dispatch path's already-built (and, when the
        descriptor is flagged, pass-optimized) plan — ``offload`` stashes
        it under the cache key before compiling, so there is exactly one
        place plans are constructed (:meth:`_plan_for`). ``traced`` builds
        the *eager* span-emitting sim interpreter (never jitted: its whole
        point is measuring per-round host time).
        """
        from repro.offload import backends

        if plan is None:
            raise ValueError(
                "planned compile without a stashed plan; dispatch through "
                "offload(), which builds it via _plan_for"
            )
        if axis_name is not None and (
            isinstance(axis_name, str) or len(axis_name) != len(desc.axes)
        ):
            raise ValueError(
                f"planned descriptor spans axes {desc.axes}; pass one mesh "
                f"axis name per axis (got {axis_name!r})"
            )
        bname, _ = self._resolve_backend(desc, plan, axis_name)
        backend = backends.get_backend(bname)
        tag = (
            bname
            if bname != backends.default_backend_name(axis_name)
            else None
        )
        if axis_name is None:
            fn = backend.lower(plan, op, traced=traced)
            # the traced interpreters are eager on purpose
            return (fn if traced else jax.jit(fn)), tag
        return backend.lower(plan, op, axis_names=tuple(axis_name)), tag

    @staticmethod
    def _build_sim(
        coll: CollType, op: AssocOp, algo: str, p: int, root: int
    ) -> Callable[[PyTree], PyTree]:
        if coll == CollType.SCAN:
            return lambda x: sim_scan(x, op, p, algorithm=algo, inclusive=True)
        if coll == CollType.EXSCAN:
            return lambda x: sim_scan(
                x, op, p, algorithm=algo, inclusive=False
            )
        if coll == CollType.REDUCE:
            return lambda x: reduce_schedule(
                alg.SimBackend(p), x, op, root=root, algorithm=algo
            )
        if coll == CollType.ALLREDUCE:
            return lambda x: allreduce_schedule(
                alg.SimBackend(p), x, op, algorithm=algo
            )
        if coll == CollType.BARRIER:
            return lambda _x: barrier_schedule(alg.SimBackend(p), algorithm=algo)
        raise ValueError(f"unknown coll_type {coll!r}")

    @staticmethod
    def _build_spmd(
        coll: CollType, op: AssocOp, algo: str, axis_name: str, root: int
    ) -> Callable[[PyTree], PyTree]:
        if coll == CollType.SCAN:
            return lambda x: dist_scan(x, op, axis_name, algorithm=algo)
        if coll == CollType.EXSCAN:
            return lambda x: dist_exscan(x, op, axis_name, algorithm=algo)
        if coll == CollType.REDUCE:
            return lambda x: reduce_schedule(
                alg.SpmdBackend(axis_name), x, op, root=root, algorithm=algo
            )
        if coll == CollType.ALLREDUCE:
            return lambda x: allreduce_schedule(
                alg.SpmdBackend(axis_name), x, op, algorithm=algo
            )
        if coll == CollType.BARRIER:
            return lambda _x: barrier_schedule(
                alg.SpmdBackend(axis_name), algorithm=algo
            )
        raise ValueError(f"unknown coll_type {coll!r}")
