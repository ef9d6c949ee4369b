"""The lowering-backend registry: how a ``CollectivePlan`` becomes code.

``planner.py`` owns the plan IR and the two op-per-round lowerings
(``lower_sim``, ``lower_spmd``); this module owns the *contract* those
lowerings satisfy, which used to be implicit in mode branches spread across
the engine, the passes, and the tuner. A :class:`LoweringBackend` exposes:

  name           registry key ("sim", "spmd", "pallas")
  capabilities   can this backend lower this plan (under these axis names)?
                 Returns ``(ok, reason)`` with a stable reason token so the
                 engine can attribute fallbacks in telemetry.
  lower          plan -> schedule callable, same calling convention as the
                 planner lowerings (stacked ``(p, ...)`` leaves without
                 ``axis_names``, per-rank under ``shard_map`` with them)
  fingerprint    extra cache-key fields. Empty for the mode defaults, so
                 every pre-registry cache key (and the broker's group keys)
                 stays byte-identical; a non-default backend contributes
                 ``(("backend", name),)`` and gets its own cache rows.

``resolve`` is the single soft-fallback point: ask for a backend by name,
get the default back (plus the capability-miss reason) when the plan is
outside the named backend's support — the engine counts those in
``EngineTelemetry.backend_fallbacks``.

The legacy two-level hierarchical entry points (previously
``repro.offload.hierarchical``) live here too: they are exactly the
registry-backed API applied to a 2-axis plan, so the thin-wrapper module
was folded in.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Protocol, Sequence, Tuple

import jax

from repro.core.operators import AssocOp, get_operator
from repro.core.scan_collective import _payload_bytes
from repro.offload.planner import (
    CollectivePlan,
    build_plan,
    lower_sim,
    lower_spmd,
)

PyTree = Any

#: name the wire format / descriptors use for "whatever the mode default
#: is" — encodes as backend id 0, so default descriptors keep their bytes
DEFAULT_BACKEND = ""


class LoweringBackend(Protocol):
    """The contract a plan lowering plugs into the registry with."""

    name: str

    def capabilities(
        self,
        plan: CollectivePlan,
        axis_names: Optional[Sequence[str]] = None,
    ) -> Tuple[bool, str]:
        """``(ok, reason)`` — can this backend lower ``plan``? ``reason``
        is a stable telemetry token when it can't ("" when it can)."""
        ...

    def lower(
        self,
        plan: CollectivePlan,
        op: "AssocOp | str | None" = None,
        *,
        axis_names: Optional[Sequence[str]] = None,
        traced: bool = False,
    ) -> Callable:
        """Compile ``plan`` to a schedule callable."""
        ...

    def fingerprint(self) -> Tuple[Tuple[str, str], ...]:
        """Cache-key fields this backend adds. MUST be empty for the mode
        defaults (key stability); non-defaults return (("backend", name),)."""
        ...


@dataclasses.dataclass(frozen=True)
class SimLowering:
    """Op-per-round interpreter over stacked leaves (the engine's sim mode)."""

    name: str = "sim"

    def capabilities(self, plan, axis_names=None):
        if axis_names is not None:
            return False, "needs_stacked_input"
        return True, ""

    def lower(self, plan, op=None, *, axis_names=None, traced=False):
        return lower_sim(plan, op, traced=traced)

    def fingerprint(self):
        return ()


@dataclasses.dataclass(frozen=True)
class SpmdLowering:
    """Op-per-round ppermute schedule inside shard_map (spmd/driver modes)."""

    name: str = "spmd"

    def capabilities(self, plan, axis_names=None):
        if axis_names is None:
            return False, "needs_axis_names"
        return True, ""

    def lower(self, plan, op=None, *, axis_names=None, traced=False):
        return lower_spmd(plan, axis_names, op)

    def fingerprint(self):
        return ()


@dataclasses.dataclass(frozen=True)
class PallasLowering:
    """Fused-kernel backend: every exchange round of a comm phase runs
    inside one Pallas kernel (``repro.kernels.pallas_collective``)."""

    name: str = "pallas"

    def capabilities(self, plan, axis_names=None):
        from repro.kernels import pallas_collective

        return pallas_collective.supports_plan(plan, axis_names)

    def lower(self, plan, op=None, *, axis_names=None, traced=False):
        from repro.kernels import pallas_collective

        return pallas_collective.lower_pallas(
            plan, op, axis_names=axis_names, traced=traced
        )

    def fingerprint(self):
        return (("backend", self.name),)


_REGISTRY: Dict[str, LoweringBackend] = {}


def register_backend(backend: LoweringBackend) -> LoweringBackend:
    _REGISTRY[backend.name] = backend
    return backend


def backend_names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def default_backend_name(
    axis_names: Optional[Sequence[str]] = None,
) -> str:
    """The backend a mode resolves to when none is named: the op-per-round
    interpreter for stacked inputs, the ppermute schedule under shard_map."""
    return "sim" if axis_names is None else "spmd"


def get_backend(name: str) -> LoweringBackend:
    key = name or DEFAULT_BACKEND
    if key == DEFAULT_BACKEND:
        raise ValueError(
            "the default backend is mode-dependent; resolve it with "
            "default_backend_name(axis_names)"
        )
    try:
        return _REGISTRY[key]
    except KeyError:
        raise ValueError(
            f"unknown lowering backend {name!r}; registered: "
            f"{', '.join(backend_names())}"
        ) from None


def resolve(
    name: str,
    plan: CollectivePlan,
    axis_names: Optional[Sequence[str]] = None,
) -> Tuple[LoweringBackend, str]:
    """Resolve ``name`` for ``plan``, soft-falling back to the mode default.

    Returns ``(backend, fallback_reason)``; ``fallback_reason`` is "" when
    the named backend (or the default, for ``name == ""``) was used, and
    the capability-miss token when the request fell back — the engine
    counts those per reason in telemetry. Unknown names raise (a typo is a
    bug, a capability miss is not).
    """
    default = _REGISTRY[default_backend_name(axis_names)]
    if (name or DEFAULT_BACKEND) == DEFAULT_BACKEND:
        return default, ""
    backend = get_backend(name)
    if backend.name == default.name:
        return default, ""
    ok, reason = backend.capabilities(plan, axis_names)
    if ok:
        return backend, ""
    return default, reason or "unsupported"


register_backend(SimLowering())
register_backend(SpmdLowering())
register_backend(PallasLowering())


# ---------------------------------------------------------------------------
# Two-level hierarchical entry points (folded in from offload/hierarchical)
# ---------------------------------------------------------------------------
#
# The original module hand-rolled the classic block-scan decomposition
# (intra-row scan, carry exscan along the orthogonal axis, guarded local
# combine) for 2D meshes; that schedule is now just a 2-axis plan lowered
# through the registry default. With global rank order outer-major
# (global = outer * p_inner + inner) the result equals the flat single-axis
# scan over p_outer * p_inner ranks — bitwise, because carries always enter
# the combine on the left.


def _two_level_plan(op, sizes, payload_bytes, *, inclusive, algorithms):
    return build_plan(
        "SCAN" if inclusive else "EXSCAN",
        sizes,
        op,
        payload_bytes,
        order=(0, 1),
        level_algorithms=algorithms,
    )


def dist_hierarchical_scan(
    x: PyTree,
    op: "AssocOp | str",
    inner_axis: str,
    outer_axis: str,
    *,
    inclusive: bool = True,
    inner_algorithm: str = "auto",
    outer_algorithm: str = "auto",
) -> PyTree:
    """Two-level scan across ``outer_axis``-major ``inner_axis``-minor order.

    Call inside ``shard_map`` over a mesh with both axes active. Equivalent
    to a flat scan over the p_outer * p_inner ranks in (outer, inner) order,
    but each phase's schedule only ever spans one axis — which is what keeps
    every hop on a physical ring of the 2D torus.
    """
    op = get_operator(op)
    axis_names = (outer_axis, inner_axis)
    plan = _two_level_plan(
        op,
        (jax.lax.axis_size(outer_axis), jax.lax.axis_size(inner_axis)),
        _payload_bytes(x),
        inclusive=inclusive,
        algorithms=(outer_algorithm, inner_algorithm),
    )
    backend, _ = resolve(DEFAULT_BACKEND, plan, axis_names)
    return backend.lower(plan, op, axis_names=axis_names)(x)


def sim_hierarchical_scan(
    stacked: PyTree,
    op: "AssocOp | str",
    p_outer: int,
    p_inner: int,
    *,
    inclusive: bool = True,
    inner_algorithm: str = "hillis_steele",
    outer_algorithm: str = "hillis_steele",
) -> PyTree:
    """Single-device realization over stacked (p_outer, p_inner, ...) leaves."""
    op = get_operator(op)
    plan = _two_level_plan(
        op,
        (p_outer, p_inner),
        _payload_bytes(stacked),
        inclusive=inclusive,
        algorithms=(outer_algorithm, inner_algorithm),
    )
    backend, _ = resolve(DEFAULT_BACKEND, plan)
    flat = flat_equivalent(stacked, p_outer, p_inner)
    out = backend.lower(plan, op)(flat)
    return jax.tree.map(
        lambda a: a.reshape((p_outer, p_inner) + a.shape[1:]), out
    )


def flat_equivalent(
    stacked_2d: PyTree, p_outer: int, p_inner: int
) -> PyTree:
    """Reshape a (p_outer, p_inner, ...) stacked pytree to the flat
    (p_outer * p_inner, ...) layout the hierarchical result must match."""
    return jax.tree.map(
        lambda a: a.reshape((p_outer * p_inner,) + a.shape[2:]), stacked_2d
    )
