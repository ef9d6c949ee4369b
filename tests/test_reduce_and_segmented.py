"""Segmented scans (Blelloch, paper refs [8,9]) and the descriptor's other
coll_types (Reduce/Allreduce/Barrier) on the same schedule machinery."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ALGORITHMS, MAX, SUM, segmented_operator, sim_scan

GENERIC = [a for a in sorted(ALGORITHMS) if a != "invertible_doubling"]


def _seg_cumsum(vals, flags):
    out = np.zeros_like(vals)
    acc = 0.0
    for i, (v, f) in enumerate(zip(vals, flags)):
        acc = v if f else acc + v
        out[i] = acc
    return out


@settings(max_examples=25, deadline=None)
@given(
    p=st.integers(1, 16),
    algo=st.sampled_from(GENERIC),
    data=st.data(),
)
def test_segmented_sum_matches_loop(p, algo, data):
    vals = np.asarray(
        data.draw(st.lists(st.floats(-4, 4, width=32), min_size=p, max_size=p)),
        np.float32,
    )
    flags = np.asarray(
        data.draw(st.lists(st.integers(0, 1), min_size=p, max_size=p)),
        np.float32,
    )
    op = segmented_operator(SUM)
    got, _ = sim_scan(
        (jnp.asarray(vals)[:, None], jnp.asarray(flags)), op, p, algorithm=algo
    )
    want = _seg_cumsum(vals, flags)
    np.testing.assert_allclose(np.asarray(got).ravel(), want, atol=1e-4)


def test_segmented_max():
    op = segmented_operator(MAX)
    vals = jnp.asarray([3.0, 1.0, 5.0, -2.0, 0.0, 4.0])[:, None]
    flags = jnp.asarray([1, 0, 0, 1, 0, 0], jnp.float32)
    got, _ = sim_scan((vals, flags), op, 6, algorithm="sklansky")
    np.testing.assert_allclose(
        np.asarray(got).ravel(), [3, 3, 5, -2, 0, 4], atol=0
    )


def test_segmented_associativity_property():
    """The lifted combine must be associative (schedule-independence)."""
    rng = np.random.default_rng(0)
    op = segmented_operator(SUM)
    for _ in range(50):
        elems = [
            (jnp.asarray(rng.normal(size=(2,)).astype(np.float32)),
             jnp.asarray(float(rng.integers(0, 2)), jnp.float32))
            for _ in range(3)
        ]
        a, b, c = elems
        left = op.combine(op.combine(a, b), c)
        right = op.combine(a, op.combine(b, c))
        np.testing.assert_allclose(np.asarray(left[0]), np.asarray(right[0]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(left[1]), np.asarray(right[1]))


def test_reduce_allreduce_barrier_spmd(subprocess_runner):
    subprocess_runner("repro.testing.reduce_check")
