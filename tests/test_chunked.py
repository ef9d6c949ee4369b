"""Chunked-streaming tests: bitwise chunked-vs-unchunked lowering for every
CollType / axis order / chunk count, the C=1 byte- and cache-key-stability
regression (a chunks=1 descriptor must encode and compile exactly like the
pre-chunking wire form), the chunk-selection pass's payload threshold, the
tuned schedule winner resolving through ``make_descriptor``, and the
algorithm-level pipeline helpers.

Bitwise equality across chunk boundaries requires exact arithmetic, so value
strategies stick to integers, exactly like the planner/passes tests.
"""

import dataclasses
import itertools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import SSD, CollType, CollectiveDescriptor, get_operator
from repro.core import algorithms as alg
from repro.core.packet import _CHUNK_WORDS, _OPT_WORDS
from repro.core.selector import set_active_tuning
from repro.offload import (
    CHUNK_CANDIDATES,
    OffloadEngine,
    TuningCache,
    build_plan,
    choose_schedule,
    lower_sim,
    optimize_plan,
    select_chunking,
)
from hypothesis import given, settings, strategies as st

MESHES = [(8,), (2, 4), (4, 2), (2, 2, 2), (2, 2, 4), (2, 8)]
CHUNKS = (1, 2, 4, 8)


@pytest.fixture(autouse=True)
def _no_active_tuning():
    set_active_tuning(None)
    yield
    set_active_tuning(None)


def _orders(k, idx):
    perms = list(itertools.permutations(range(k)))
    return perms[idx % len(perms)]


def _int_payload(p, n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(-6, 7, size=(p, n)).astype(np.float32))


# ------------------------------------------- bitwise: chunked == unchunked


@settings(max_examples=40, deadline=None)
@given(
    mesh_idx=st.integers(0, len(MESHES) - 1),
    coll_idx=st.integers(0, len(CollType) - 1),
    chunk_idx=st.integers(0, len(CHUNKS) - 1),
    order_idx=st.integers(0, 5),
    seed=st.integers(0, 10_000),
)
def test_chunked_bitwise_equals_unchunked_all_colltypes(
    mesh_idx, coll_idx, chunk_idx, order_idx, seed
):
    """Every CollType, mesh, axis order, and C in {1,2,4,8}: the chunked
    lowering's result equals the unchunked plan's, bit for bit. CollTypes
    with no pipelined phase (REDUCE/ALLREDUCE/BARRIER) must be unaffected
    by the chunking knob, which the same comparison proves."""
    sizes = MESHES[mesh_idx]
    coll = list(CollType)[coll_idx].name
    chunks = CHUNKS[chunk_idx]
    order = _orders(len(sizes), order_idx)
    p = int(np.prod(sizes))
    # ragged split: 13 is not divisible by any C > 1
    n = 13 if seed % 2 else 32
    x = _int_payload(p, n, seed)
    root = seed % p
    base = build_plan(coll, sizes, "sum", n * 4, order=order, root=root)
    chunked = dataclasses.replace(base, chunking=chunks)
    arg = None if coll == "BARRIER" else x
    got_base = np.asarray(lower_sim(base)(arg))
    got_chunked = np.asarray(lower_sim(chunked)(arg))
    np.testing.assert_array_equal(got_chunked, got_base)


@settings(max_examples=24, deadline=None)
@given(
    mesh_idx=st.integers(0, len(MESHES) - 1),
    inclusive=st.booleans(),
    chunk_idx=st.integers(1, len(CHUNKS) - 1),
    order_idx=st.integers(0, 5),
    seed=st.integers(0, 10_000),
)
def test_chunked_optimized_bitwise(
    mesh_idx, inclusive, chunk_idx, order_idx, seed
):
    """Chunking composed with the full pass pipeline (fused
    SCAN+TOTAL phases take the chunked_scan_total_schedule path): the
    chunked optimized plan equals both the unchunked optimized plan and
    the raw plan, bitwise, under jit."""
    sizes = MESHES[mesh_idx]
    chunks = CHUNKS[chunk_idx]
    order = _orders(len(sizes), order_idx)
    coll = "SCAN" if inclusive else "EXSCAN"
    p = int(np.prod(sizes))
    x = _int_payload(p, 24, seed)
    raw = build_plan(coll, sizes, "sum", 96, order=order)
    opt = optimize_plan(raw)
    opt_chunked = dataclasses.replace(opt, chunking=chunks)
    got_raw = np.asarray(jax.jit(lower_sim(raw))(x))
    got_opt = np.asarray(jax.jit(lower_sim(opt))(x))
    got_chunked = np.asarray(jax.jit(lower_sim(opt_chunked))(x))
    np.testing.assert_array_equal(got_opt, got_raw)
    np.testing.assert_array_equal(got_chunked, got_raw)


@settings(max_examples=16, deadline=None)
@given(
    mesh_idx=st.integers(0, 3),
    inclusive=st.booleans(),
    chunks=st.sampled_from([2, 4]),
    seed=st.integers(0, 10_000),
)
def test_chunked_ssd_bitwise(mesh_idx, inclusive, chunks, seed):
    """Non-commutative SSD (decay, state) recurrence stays bitwise under
    chunking — chunk boundaries must not reorder the combine tree."""
    sizes = [(2, 4), (4, 2), (2, 2, 2), (2, 8)][mesh_idx]
    p = int(np.prod(sizes))
    rng = np.random.default_rng(seed)
    a = jnp.asarray(
        rng.choice([0.5, 1.0, 2.0], size=(p, 4)).astype(np.float32)
    )
    b = jnp.asarray(rng.integers(-4, 5, size=(p, 4)).astype(np.float32))
    coll = "SCAN" if inclusive else "EXSCAN"
    base = build_plan(coll, sizes, SSD, 32)
    chunked = dataclasses.replace(base, chunking=chunks)
    ra, rb = lower_sim(base, SSD)((a, b))
    ca, cb = lower_sim(chunked, SSD)((a, b))
    np.testing.assert_array_equal(np.asarray(ca), np.asarray(ra))
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(rb))


# ------------------------------------- C=1 wire- and cache-key stability


def test_c1_descriptor_encodes_to_pre_chunking_wire_form():
    """chunks=1 must be byte-invisible: same word count and same words as
    a descriptor built before the chunks field existed."""
    eng = OffloadEngine()
    desc = eng.make_descriptor(
        "SCAN", axes=(2, 4), payload_bytes=1024, op="sum", chunks=1
    )
    words = desc.encode()
    assert len(words) == _OPT_WORDS  # 16 — no 17th chunk word at C=1
    legacy = dataclasses.replace(desc, chunks=1).encode()
    np.testing.assert_array_equal(np.asarray(words), np.asarray(legacy))
    # decoding the 16-word form yields chunks=1, i.e. the same descriptor
    assert CollectiveDescriptor.decode(words) == desc


def test_chunked_descriptor_round_trips_17_words():
    eng = OffloadEngine()
    desc = eng.make_descriptor(
        "SCAN", axes=(2, 4), payload_bytes=1 << 20, op="sum", chunks=4
    )
    words = desc.encode()
    assert len(words) == _CHUNK_WORDS  # 17
    assert words[_CHUNK_WORDS - 1] == 4
    assert CollectiveDescriptor.decode(words) == desc


def test_chunks_require_planned_descriptor():
    eng = OffloadEngine()
    with pytest.raises(ValueError):
        eng.make_descriptor("SCAN", p=8, payload_bytes=64, chunks=2)
    with pytest.raises(ValueError):
        CollectiveDescriptor(coll_type=CollType.SCAN, comm_size=8, chunks=2)
    with pytest.raises(ValueError):
        CollectiveDescriptor(coll_type=CollType.SCAN, comm_size=8, chunks=0)


def test_c1_cache_key_stable_and_chunked_keys_distinct():
    """Cache-key regression: a chunks=1 descriptor and its 16-word wire
    decode land in the SAME compiled-schedule cache entry (C=1 compiles to
    the identical schedule as before this feature), while a chunked
    descriptor gets its own entry."""
    eng = OffloadEngine()
    x = _int_payload(8, 16, 3)
    d1 = eng.make_descriptor(
        "SCAN", axes=(2, 4), payload_bytes=64, op="sum", chunks=1
    )
    y1 = np.asarray(eng.offload(d1, x))
    assert (eng.telemetry.hits, eng.telemetry.misses) == (0, 1)
    # wire round-trip (16 words, no chunk word) must hit the same entry
    y2 = np.asarray(eng.offload(d1.encode(), x))
    assert (eng.telemetry.hits, eng.telemetry.misses) == (1, 1)
    np.testing.assert_array_equal(y2, y1)
    # a chunked sibling is a different compiled schedule (miss) ...
    d4 = dataclasses.replace(d1, chunks=4)
    y4 = np.asarray(eng.offload(d4, x))
    assert (eng.telemetry.hits, eng.telemetry.misses) == (1, 2)
    # ... but the same bits
    np.testing.assert_array_equal(y4, y1)
    # and its own 17-word wire form hits the chunked entry
    np.asarray(eng.offload(d4.encode(), x))
    assert (eng.telemetry.hits, eng.telemetry.misses) == (2, 2)


@pytest.mark.parametrize("coll", ["SCAN", "EXSCAN", "ALLREDUCE"])
def test_engine_chunked_dispatch_bitwise(coll):
    """End-to-end engine dispatch: explicit chunks=2 planned descriptor is
    bitwise-equal to the chunks=1 dispatch for pipelined and
    non-pipelined CollTypes alike."""
    eng = OffloadEngine()
    x = _int_payload(8, 32, 7)
    kw = dict(axes=(2, 4), payload_bytes=128, op="sum")
    y1 = np.asarray(
        eng.offload(eng.make_descriptor(coll, chunks=1, **kw), x)
    )
    y2 = np.asarray(
        eng.offload(eng.make_descriptor(coll, chunks=2, **kw), x)
    )
    np.testing.assert_array_equal(y2, y1)


# ------------------------------------------- chunk-selection pass + tuning


def test_select_chunking_payload_threshold():
    """The cost model keeps C=1 below the crossover and picks C>1 above
    it, only for plans with a pipelined (doubling-scan) phase."""
    plan = build_plan("SCAN", (2, 8), "sum", 1024)
    assert select_chunking(plan, 1024).chunking == 1
    big = select_chunking(plan, 4 << 20).chunking
    assert big > 1
    assert big in CHUNK_CANDIDATES
    # pure reduction: no pipelined phase, chunking stays 1 at any payload
    red = build_plan("ALLREDUCE", (2, 8), "sum", 4 << 20)
    assert select_chunking(red, 4 << 20).chunking == 1


def test_select_chunking_monotone_engagement():
    """Chunk counts never decrease as payload grows (the pipelined cost
    model is a sum of a C-decreasing and a C-increasing term)."""
    plan = build_plan("SCAN", (2, 2, 2), "sum", 1024)
    picks = [
        select_chunking(plan, b).chunking
        for b in (1 << 10, 1 << 14, 1 << 18, 1 << 22, 1 << 24)
    ]
    assert picks == sorted(picks)


def test_choose_schedule_prefers_measured_winner():
    """An active tuning table with a recorded schedule winner overrides
    the cost model, and make_descriptor(optimize='auto') inherits it."""
    coll, sizes, payload = "scan", (2, 4), 1024
    cache = TuningCache()
    # cost model alone would never chunk a 1KB payload ...
    assert choose_schedule(coll, sizes, payload)[1] == 1
    # ... but a measured table that saw (optimized, C=4) win rules
    cache.record_schedule(coll, sizes, False, 1, payload, 9e-4)
    cache.record_schedule(coll, sizes, True, 1, payload, 8e-4)
    cache.record_schedule(coll, sizes, True, 4, payload, 2e-4)
    assert cache.schedule_winner(coll, sizes, payload) == (True, 4)
    cache.activate()
    try:
        assert choose_schedule(coll, sizes, payload) == (True, 4)
        eng = OffloadEngine()
        desc = eng.make_descriptor(
            "SCAN", axes=sizes, payload_bytes=payload, op="sum"
        )
        assert (desc.optimized, desc.chunks) == (True, 4)
        x = _int_payload(8, 16, 11)
        raw = np.asarray(
            eng.offload(
                eng.make_descriptor(
                    "SCAN", axes=sizes, payload_bytes=payload, op="sum",
                    optimize=False, chunks=1,
                ),
                x,
            )
        )
        np.testing.assert_array_equal(np.asarray(eng.offload(desc, x)), raw)
    finally:
        set_active_tuning(None)


def test_schedule_winner_tie_break_prefers_unchunked():
    """Equal measurements: the winner is the simpler schedule (optimized
    first, then the smaller chunk count) so noise cannot flip C upward."""
    cache = TuningCache()
    cache.record_schedule("scan", (2, 4), True, 1, 1024, 5e-4)
    cache.record_schedule("scan", (2, 4), True, 8, 1024, 5e-4)
    assert cache.schedule_winner("scan", (2, 4), 1024) == (True, 1)


# ------------------------------------------------- algorithm-level helpers


def test_chunk_bounds_and_split_concat_round_trip():
    assert alg.chunk_bounds(13, 4) == [0, 3, 6, 9, 13]
    assert alg.chunk_bounds(8, 4) == [0, 2, 4, 6, 8]
    x = _int_payload(4, 13, 0)
    parts = alg.split_chunks(x, 4)
    assert len(parts) == 4
    np.testing.assert_array_equal(
        np.asarray(alg.concat_chunks(parts)), np.asarray(x)
    )


@pytest.mark.parametrize("chunks", [2, 4, 8])
@pytest.mark.parametrize("algo", sorted(alg.DOUBLING_ALGORITHMS))
def test_run_chunked_matches_unchunked(algo, chunks):
    """Direct algorithm-level pipeline: run_chunked over a SimBackend
    equals the plain doubling scan, bitwise, for both doubling variants."""
    p, n = 8, 24
    x = _int_payload(p, n, chunks)
    op = get_operator("sum")
    fn = alg.get_algorithm(algo)
    backend = alg.SimBackend(p)
    want = np.asarray(fn(backend, x, op))
    got = np.asarray(
        alg.run_chunked(lambda t: fn(backend, t, op), x, chunks)
    )
    np.testing.assert_array_equal(got, want)


def test_chunked_scan_schedule_requires_chunkable_payload():
    """A payload whose trailing axis can't be split (fewer elements than
    chunks) falls back to the unchunked path rather than failing."""
    p = 8
    x = _int_payload(p, 1, 5)  # last dim 1 < chunks
    op = get_operator("sum")
    backend = alg.SimBackend(p)
    want = np.asarray(alg.hillis_steele(backend, x, op))
    got = np.asarray(
        alg.run_chunked(
            lambda t: alg.hillis_steele(backend, t, op), x, 4
        )
    )
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- per-(round, chunk) spans


def test_traced_chunked_dispatch_labels_round_spans():
    """A traced chunked dispatch stays bitwise-identical to the untraced
    one and labels its pipelined round spans with (chunk, chunk_round)
    coordinates; the unchunked dispatch emits no chunk labels at all."""
    from repro.obs import tracing as obs_tracing

    eng = OffloadEngine()
    x = _int_payload(8, 32, 13)
    kw = dict(axes=(2, 4), payload_bytes=128, op="sum", optimize=True)
    d1 = eng.make_descriptor("scan", chunks=1, **kw)
    d2 = eng.make_descriptor("scan", chunks=2, **kw)
    want = np.asarray(eng.offload(d1, x))
    try:
        with obs_tracing.tracing() as tracer:
            got = np.asarray(eng.offload(d2, x))
        np.testing.assert_array_equal(got, want)
        rounds = [s for s in tracer.spans() if s.cat == "round"]
        assert rounds
        labelled = [s for s in rounds if "chunk" in s.args]
        assert labelled, "chunked dispatch emitted no chunk-labelled rounds"
        for s in labelled:
            assert 0 <= s.args["chunk"] < 2
            assert s.args["chunk_round"] >= 0
        with obs_tracing.tracing() as tracer:
            np.testing.assert_array_equal(
                np.asarray(eng.offload(d1, x)), want
            )
        assert not any(
            "chunk" in s.args
            for s in tracer.spans()
            if s.cat == "round"
        )
    finally:
        obs_tracing.set_tracer(None)
