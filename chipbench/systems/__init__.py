"""Drivers of the program under test, one per kind of system a
configuration names (``"system"`` in ``configs/<name>.json``)."""

from __future__ import annotations

import dataclasses
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
    kind = config["system"]
    if kind == "offload":
        from chipbench.systems.offload import OffloadCell

        return OffloadCell(config, workload, **kw)
    if kind == "trainer":
        from chipbench.systems.trainer import TrainerCell

        return TrainerCell(config, workload, **kw)
    raise ValueError(f"unknown system {kind!r} in configuration {config['name']!r}")


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
