"""Per-kernel shape/dtype sweeps vs. the ref.py oracles (interpret mode)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
pytest.importorskip("hypothesis", reason="hypothesis not installed in this container")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ------------------------------------------------------------- histogram ---
@pytest.mark.parametrize("n,d,p", [(10, 3, 4), (513, 16, 7), (1000, 129, 300),
                                   (2048, 256, 256)])
def test_hist_kernel_matches_ref(n, d, p):
    rng = np.random.default_rng(n + d + p)
    codes = jnp.asarray(rng.integers(0, p, size=n, dtype=np.int32))
    vals = jnp.asarray(rng.uniform(0, 2, size=(n, d)).astype(np.float32))
    got = ops.segment_hist(codes, vals, p)
    want = ref.segment_hist_ref(codes, vals, p)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-3)


def test_hist_kernel_drops_negative_codes():
    codes = jnp.asarray(np.array([0, -1, 2, -1], np.int32))
    vals = jnp.ones((4, 5), jnp.float32)
    got = ops.segment_hist(codes, vals, 3)
    want = np.zeros((3, 5), np.float32)
    want[0] = 1.0
    want[2] = 1.0
    np.testing.assert_allclose(np.asarray(got), want)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_hist_kernel_property(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(1, 700))
    d = int(rng.integers(1, 40))
    p = int(rng.integers(1, 50))
    codes = jnp.asarray(rng.integers(0, p, size=n, dtype=np.int32))
    vals = jnp.asarray(rng.uniform(-1, 1, size=(n, d)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(ops.segment_hist(codes, vals, p)),
                               np.asarray(ref.segment_hist_ref(codes, vals, p)),
                               rtol=1e-4, atol=1e-3)


# ------------------------------------------------------------------ bdeu ---
@pytest.mark.parametrize("q,r", [(1, 2), (3, 4), (100, 3), (600, 7), (1024, 33)])
@pytest.mark.parametrize("ess", [1.0, 10.0])
def test_bdeu_kernel_matches_ref(q, r, ess):
    rng = np.random.default_rng(q * r)
    nijk = jnp.asarray(rng.integers(0, 30, size=(q, r)).astype(np.float32))
    got = ops.bdeu(nijk, ess=ess)
    want = ref.bdeu_ref(nijk, ess, q, r)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4, atol=1e-2)


def test_bdeu_kernel_matches_core_scorer():
    from repro.core.bdeu import bdeu_score_2d
    rng = np.random.default_rng(0)
    nijk = jnp.asarray(rng.integers(0, 20, size=(36, 4)).astype(np.float32))
    np.testing.assert_allclose(float(ops.bdeu(nijk, ess=1.0)),
                               float(bdeu_score_2d(nijk, ess=1.0)),
                               rtol=1e-4, atol=1e-2)
