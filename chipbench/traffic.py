"""Traffic and payload generators: every input of a run comes from its seed.

Open loop (``open_loop_zipf``): a Poisson arrival process conditioned on its
count, ``round(rate * seconds)`` arrivals placed uniformly in the window and
sorted, so every seed offers the same amount of work in another order.
Tenants' arrival shares follow Zipf(s); each tenant's count is its share of
the total (largest remainder), and the seed shuffles the order. Each tenant
issues one fixed collective shape, assigned from the workload's own
``shape_seed``, so the mix of shapes is the same for every run seed.

Closed loop (``closed_loop``): one caller cycles through the workload's
operation variants; the seed draws the payload values.

Packed token batches (``packed_batches``) reproduce the program's data
layer (``repro.data.pipeline``): seeded synthetic documents packed into
fixed-length rows at exclusive-scan offsets.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Tuple

import numpy as np

def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one run seed (any size of seed)."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


@dataclasses.dataclass(frozen=True)
class Shape:
    coll: str
    op: str
    dtype: str
    bytes_per_rank: int

    @property
    def count(self) -> int:
        return max(1, self.bytes_per_rank // 4)


def host_payloads(
    rng: np.random.Generator, m: int, p: int, shape: Shape
) -> np.ndarray:
    """``m`` stacked ``(p, count)`` payloads. int32 values span the whole
    range, so sums wrap and any narrower integer type gives other bits;
    float32 values are standard normal."""
    if shape.dtype == "int32":
        return rng.integers(
            -(2**31), 2**31, size=(m, p, shape.count), dtype=np.int32
        )
    if shape.dtype == "float32":
        return rng.standard_normal((m, p, shape.count), dtype=np.float32)
    raise ValueError(f"unsupported payload dtype {shape.dtype!r}")


def zipf_counts(n: int, tenants: int, s: float) -> np.ndarray:
    """Arrivals per tenant: Zipf(s) shares of ``n``, largest remainder."""
    w = 1.0 / np.arange(1, tenants + 1, dtype=np.float64) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
    return counts


def tenant_shapes(workload: Dict) -> List[Shape]:
    """Each tenant's fixed shape, from the workload's ``shape_seed``."""
    rng = np.random.default_rng(int(workload["shape_seed"]))
    kinds = workload["ops"]
    sizes = workload["bytes_per_rank"]
    out = []
    for _ in range(int(workload["tenants"])):
        coll, op, dtype = kinds[int(rng.integers(len(kinds)))]
        out.append(Shape(coll, op, dtype, int(sizes[int(rng.integers(len(sizes)))])))
    return out


def open_loop_schedule(
    workload: Dict, seed: int, seconds: float, rate: float
) -> Tuple[np.ndarray, np.ndarray]:
    """(due seconds from the window's start, tenant index) per arrival."""
    n = int(round(rate * seconds))
    rng = rng_for(seed, 1)
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    counts = zipf_counts(n, int(workload["tenants"]), float(workload["zipf_s"]))
    tenant = rng.permutation(np.repeat(np.arange(len(counts)), counts))
    return due, tenant


def closed_loop_variants(workload: Dict) -> List[Shape]:
    """The caller's cycle: for each size, each operation in turn."""
    return [
        Shape(coll, op, dtype, int(b))
        for b in workload["bytes_per_rank"]
        for coll, op, dtype in workload["ops"]
    ]


def packed_batches(
    vocab_size: int, seq_len: int, batch: int, seed: int,
    mean_doc_len: int = 512, pad_id: int = 0, eos_id: int = 1,
) -> Iterator[Dict[str, np.ndarray]]:
    """Infinite ``{tokens, labels}`` batches of packed synthetic documents.

    Documents are incrementing runs mod the vocabulary from a random start
    with 10% uniform noise tokens, geometric lengths (mean ``mean_doc_len``,
    clipped to [8, 8 * mean]), ending in ``eos_id``; they are laid end to end
    at exclusive-scan offsets into rows of ``seq_len + 1`` tokens, and each
    row gives ``tokens = row[:-1]``, ``labels = row[1:]``."""
    rng = rng_for(seed, 2)
    lo, span = 2, vocab_size - 2

    def document() -> np.ndarray:
        n = int(np.clip(rng.geometric(1.0 / mean_doc_len), 8, 8 * mean_doc_len))
        doc = (lo + (int(rng.integers(0, span)) + np.arange(n)) % span).astype(np.int32)
        noise = rng.random(n) < 0.1
        doc[noise] = rng.integers(lo, vocab_size, size=int(noise.sum()), dtype=np.int32)
        doc[-1] = eos_id
        return doc

    width = seq_len + 1
    ready: List[np.ndarray] = []
    while True:
        while len(ready) < batch:
            docs, total = [], 0
            while total < 2 * width:
                docs.append(document())
                total += len(docs[-1])
            lens = np.array([len(d) for d in docs])
            offsets = np.concatenate([[0], np.cumsum(lens)[:-1]])
            rows = -(-int(lens.sum()) // width)
            flat = np.full(rows * width, pad_id, dtype=np.int32)
            for d, off in zip(docs, offsets):
                flat[off : off + len(d)] = d
            ready.extend(flat.reshape(rows, width))
        rows_ = np.stack(ready[:batch])
        ready = ready[batch:]
        yield {"tokens": rows_[:, :-1].copy(), "labels": rows_[:, 1:].copy()}


def take(it: Iterator, n: int) -> List:
    return [next(it) for _ in range(n)]

