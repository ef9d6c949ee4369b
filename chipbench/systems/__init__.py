"""Drivers of the program under test, one per kind of system a
configuration names (``"system"`` in ``configs/<name>.json``).

A new system is one new file, ``systems/<system>.py``, that exports
``Cell``: a class built as ``Cell(config, workload, *, seed, seconds,
devices, scratch, tracing)`` whose instance the harness drives in this
order: ``setup()`` (everything before the window: build, inputs, warm-up),
``counters()`` (before and after the window: ``{group: {name: number}}``),
``window() -> Window``, ``spans()`` (the program's spans that started in
the window, or None), ``release()`` (the program's state freed, its
answers on the host) and ``check(control=False) -> List[Check]``. Nothing
here lists the systems: ``build`` imports ``chipbench.systems.<system>`` by
the configuration's name for it.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Window:
    """What one measured window did, on the host clock (perf_counter s)."""

    start: float
    end: float
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    attempted: int = 0
    #: requests that raised or never came back
    failed: int = 0
    #: answered, and correct, by the window's close (set by ``check``)
    correct_in_window: int = 0
    tokens: int = 0
    #: (descriptor, dispatches) per operation variant the window ran
    ops: List[Any] = dataclasses.field(default_factory=list)
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes at or under the limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def build(config: Dict[str, Any], workload: Dict[str, Any], **kw):
    """The ``Cell`` of ``chipbench.systems.<config["system"]>``, built."""
    name = f"{__name__}.{config['system']}"
    try:
        module = importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"unknown system {config['system']!r} in configuration "
                         f"{config['name']!r}") from None
    return module.Cell(config, workload, **kw)


def window_spans(window: Window) -> Optional[list]:
    """The spans of the program's tracer that started inside ``window``;
    None where no collecting tracer is installed."""
    from repro.obs import tracing

    tracer = tracing.get_tracer()
    if not tracer.enabled:
        return None
    lo, hi = window.start * 1e6, window.end * 1e6
    return [s for s in tracer.spans() if lo <= s.start_us <= hi]


def jax_key(seed: int):
    """A JAX PRNG key from a seed of any size."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF), (seed >> 32) & 0x7FFFFFFF
    )


def peak_bytes(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None
