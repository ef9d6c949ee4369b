"""Property tests for the scan-collective schedules (simulator backend).

The SimBackend has identical messaging semantics to the SPMD backend
(zero-fill on missing in-edges), so hypothesis can sweep rank counts and
operators cheaply on one device; the real-ppermute path is covered by
tests/test_dist_scan_spmd.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ALGORITHMS,
    MAX,
    SSD,
    SUM,
    CollectiveDescriptor,
    algorithm_step_count,
    cost_table,
    estimate_cost,
    get_operator,
    host_scan,
    schedule_trace,
    select_algorithm,
    sim_scan,
)

ALGOS = sorted(ALGORITHMS)
GENERIC_ALGOS = [a for a in ALGOS if a != "invertible_doubling"]


@settings(max_examples=40, deadline=None)
@given(
    p=st.integers(1, 24),
    n=st.integers(1, 5),
    algo=st.sampled_from(ALGOS),
    inclusive=st.booleans(),
    data=st.data(),
)
def test_sum_matches_cumsum(p, n, algo, inclusive, data):
    vals = data.draw(
        st.lists(
            st.lists(st.floats(-8, 8, width=32), min_size=n, max_size=n),
            min_size=p,
            max_size=p,
        )
    )
    x = np.asarray(vals, np.float32)
    want = np.cumsum(x, axis=0)
    if not inclusive:
        want = np.concatenate([np.zeros((1, n), np.float32), want[:-1]])
    got = np.asarray(
        sim_scan(jnp.asarray(x), "sum", p, algorithm=algo, inclusive=inclusive)
    )
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


@settings(max_examples=25, deadline=None)
@given(p=st.integers(1, 17), algo=st.sampled_from(GENERIC_ALGOS), data=st.data())
def test_max_scan(p, algo, data):
    # XLA's CPU backend flushes subnormals to zero; numpy keeps them
    vals = data.draw(
        st.lists(
            st.floats(-100, 100, width=32, allow_subnormal=False),
            min_size=p, max_size=p,
        )
    )
    x = np.asarray(vals, np.float32)[:, None]
    want = np.maximum.accumulate(x, axis=0)
    got = np.asarray(sim_scan(jnp.asarray(x), "max", p, algorithm=algo))
    np.testing.assert_allclose(got, want, atol=0, rtol=0)


@settings(max_examples=20, deadline=None)
@given(p=st.integers(1, 12), algo=st.sampled_from(GENERIC_ALGOS), data=st.data())
def test_ssd_noncommutative_pytree(p, algo, data):
    a = np.asarray(
        data.draw(st.lists(st.floats(0.25, 1.0, width=32), min_size=p, max_size=p)),
        np.float32,
    )[:, None]
    b = np.asarray(
        data.draw(st.lists(st.floats(-2, 2, width=32), min_size=p, max_size=p)),
        np.float32,
    )[:, None]
    A = np.empty_like(a)
    B = np.empty_like(b)
    A[0], B[0] = a[0], b[0]
    for j in range(1, p):
        A[j] = a[j] * A[j - 1]
        B[j] = a[j] * B[j - 1] + b[j]
    ga, gb = sim_scan((jnp.asarray(a), jnp.asarray(b)), SSD, p, algorithm=algo)
    np.testing.assert_allclose(np.asarray(ga), A, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gb), B, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("algo", ALGOS)
def test_step_counts_match_trace(algo):
    """The latency model's step count == the actual schedule's permute count."""
    for p in (2, 4, 8, 16):
        trace = schedule_trace(algo, p)
        # steps with no wire activity don't appear in latency; count nonempty
        nonempty = sum(1 for perm in trace if perm)
        assert nonempty <= algorithm_step_count(algo, p) + 1, (algo, p)
        assert nonempty >= 1


def test_sequential_message_economy():
    """Paper II-B1: sequential sends exactly p-1 point-to-point messages."""
    trace = schedule_trace("sequential", 8)
    total_msgs = sum(len(perm) for perm in trace)
    assert total_msgs == 7


def test_sklansky_multicast_pattern():
    """Paper Fig.3: sklansky steps contain one-to-many (repeated sources)."""
    trace = schedule_trace("sklansky", 8)
    last = trace[-1]
    srcs = [s for s, _ in last]
    assert len(srcs) != len(set(srcs)), "expected multicast (repeated source)"


def test_host_scan_equals_sim():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
    for algo in GENERIC_ALGOS:
        a = np.asarray(host_scan(x, "sum", 8, algorithm=algo))
        b = np.asarray(sim_scan(x, "sum", 8, algorithm=algo))
        np.testing.assert_allclose(a, b, atol=1e-5)


def test_selector_prefers_log_algorithms_at_scale():
    assert select_algorithm(256, 1 << 20, SUM) != "sequential"
    assert select_algorithm(256, 64, SUM) != "sequential"
    # tiny axis, tiny payload: anything goes, but must be a known algorithm
    assert select_algorithm(4, 64, SUM) in ALGORITHMS


def test_selector_respects_applicability():
    # MAX has no inverse: invertible_doubling must never be selected
    for p in (4, 16, 64, 256):
        for size in (64, 1 << 16, 1 << 24):
            assert select_algorithm(p, size, MAX) != "invertible_doubling"


def test_cost_table_monotone_in_payload():
    small = cost_table(16, 1 << 10)
    big = cost_table(16, 1 << 24)
    for k in small:
        assert big[k] > small[k]


def test_descriptor_roundtrip_and_node_type():
    d = CollectiveDescriptor(
        comm_id=3, comm_size=16, rank=7, algo_type="binomial_tree", count=256
    )
    assert CollectiveDescriptor.decode(d.encode()) == d
    assert CollectiveDescriptor(comm_size=8, rank=7).node_type.name == "ROOT"
    assert CollectiveDescriptor(comm_size=8, rank=0).node_type.name == "LEAF"
    assert CollectiveDescriptor(comm_size=8, rank=3).node_type.name == "INTERNAL"
