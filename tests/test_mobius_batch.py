"""The Möbius negative phase, batched and per query, on the host.

Three layers are pinned down:

* the transform: :func:`repro.core.mobius.superset_mobius` equals the
  Möbius matrix, superset sums invert it, and it subtracts in the dtype it
  is given while the all-true corner keeps its input;
* the assembly: :func:`repro.core.mobius.complete_ct_many` equals
  per-query :func:`~repro.core.mobius.complete_ct` under BOTH evaluation
  orders (butterfly and blockwise), hands back float64 host tables equal
  to the brute-force oracle, and reads each shared block once;
* the strategies: ``family_ct_many`` (which routes whole rounds
  through the batched negative phase) == per-family ``family_ct`` ==
  brute-force oracle for all four strategies × both executors, including
  ``k == 0`` keeps (no indicator axes — nothing to transform) and card-1
  attribute domains.
"""

import itertools

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.core import (CostStats, CountingEngine, build_lattice,
                        complete_ct, complete_ct_many, make_strategy,
                        superset_mobius)
from repro.core.engine import (CachedFullPositives, OnDemandPositives,
                               TupleIdPositives)
from repro.core.oracle import oracle_ct
from repro.core.strategies import STRATEGIES
from tests.test_executor_edge_cases import edge_case_db
from tests.test_engine_equivalence import random_db, random_keeps
from tests.test_serve import mixed_db

STRAT_X_EXEC = list(itertools.product(sorted(STRATEGIES),
                                      ("dense", "sparse")))


# ------------------------------------------------------------ transform ----

def _random_stack(rng, k, attr_shape, high=50):
    return rng.integers(0, high, size=(2,) * k + attr_shape).astype(
        np.float64)


def _mobius_matrix(k):
    """T[A, S] = (-1)^(|S|-|A|) for S a superset of A (bit 1 = relation
    true), else 0: the transform as a matrix on the flattened corners."""
    n = 1 << k
    return np.array([[(-1) ** bin(s ^ a).count("1") if s & a == a else 0
                      for s in range(n)] for a in range(n)], np.float64)


@pytest.mark.parametrize("k", [1, 2, 3, 4, 6])
@pytest.mark.parametrize("d", [1, 7, 128, 300])
def test_host_transform_matches_the_mobius_matrix(k, d):
    stack = _random_stack(np.random.default_rng(k * 100 + d), k, (d,))
    got = superset_mobius(stack, k, np.float64)
    assert isinstance(got, np.ndarray) and got.shape == stack.shape
    np.testing.assert_array_equal(
        got.reshape(1 << k, d),
        _mobius_matrix(k) @ stack.reshape(1 << k, d))


@settings(max_examples=25, deadline=None)
@given(k=st.integers(1, 5), seed=st.integers(0, 10_000))
def test_superset_sums_invert_the_transform(k, seed):
    rng = np.random.default_rng(seed)
    stack = _random_stack(rng, k, (int(rng.integers(1, 64)),))
    got = superset_mobius(stack, k, np.float64).reshape(1 << k, -1)
    zeta = np.abs(_mobius_matrix(k))          # zeta[A, S] = 1 iff S >= A
    np.testing.assert_array_equal(zeta @ got, stack.reshape(1 << k, -1))


@pytest.mark.parametrize("dtype", [np.float64, jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [1, 2, 3])
def test_host_transform_subtracts_in_dtype(dtype, k):
    """Counts past 2**24: the all-true corner, which no subtraction
    writes, keeps the input exactly; every other corner is a value of
    ``dtype`` within its rounding of the exact transform."""
    stack = _random_stack(np.random.default_rng(k), k, (3, 2),
                          high=2 ** 26) + 2 ** 25
    exact = superset_mobius(stack, k, np.float64)
    got = superset_mobius(stack, k, dtype)
    assert got.dtype == np.float64
    top = (1,) * k
    np.testing.assert_array_equal(got[top], stack[top])
    dt = jnp.dtype(dtype)
    rest = got.reshape((1 << k,) + got.shape[k:])[:-1]     # all but top
    np.testing.assert_array_equal(rest, rest.astype(dt).astype(np.float64))
    eps = float(jnp.finfo(dt).eps)
    assert np.max(np.abs(got - exact)) <= 2 * k * eps * np.max(stack)
    if dt != np.float64:
        assert np.any(got != exact)


# ------------------------------------------------------------- assembly ----

def _queries(db, rng, n_random):
    lattice = build_lattice(db.schema, 2)
    queries = []
    for point in (lattice[0], lattice[-1]):
        pool = list(point.all_ct_vars(db.schema, include_rind=True))
        queries.append((point, tuple(pool)))
        queries.append((point, ()))                       # k == 0, scalar
        queries.append((point, tuple(v for v in pool
                                     if v.kind == "attr")))  # k == 0
        for _ in range(n_random):
            k = rng.integers(1, len(pool) + 1)
            pick = rng.choice(len(pool), size=k, replace=False)
            queries.append((point, tuple(pool[i] for i in sorted(pick))))
    return queries


@pytest.mark.parametrize("ex", ["dense", "sparse"])
def test_complete_ct_many_equals_complete_ct_both_orders(ex):
    db = mixed_db()
    queries = _queries(db, np.random.default_rng(5), 3)
    for use_butterfly in (True, False):
        eng = CountingEngine(db, ex, CostStats())
        policy = OnDemandPositives(eng)
        got = complete_ct_many(queries, policy, use_butterfly=use_butterfly)
        ref_eng = CountingEngine(db, ex, CostStats())
        ref_policy = OnDemandPositives(ref_eng)
        for (point, keep), g in zip(queries, got):
            want = complete_ct(point, keep, ref_policy,
                               use_butterfly=use_butterfly)
            assert g.vars == want.vars
            np.testing.assert_array_equal(
                np.asarray(g.counts), np.asarray(want.counts),
                err_msg=f"{ex} butterfly={use_butterfly} "
                        f"keep={[str(v) for v in keep]}")


@pytest.mark.parametrize("policy_cls", [OnDemandPositives,
                                        CachedFullPositives,
                                        TupleIdPositives])
def test_complete_tables_are_exact_float64_on_the_host(policy_cls):
    """Whatever serves the positives, the joined tables are NumPy float64
    and equal the brute-force oracle exactly."""
    db = mixed_db()
    queries = _queries(db, np.random.default_rng(9), 4)
    lattice = build_lattice(db.schema, 2)
    policy = policy_cls(CountingEngine(db, "sparse", CostStats()))
    policy.precompute(lattice)
    for (point, keep), g in zip(queries, complete_ct_many(queries, policy)):
        assert isinstance(g.counts, np.ndarray)
        assert g.counts.dtype == np.float64
        np.testing.assert_array_equal(
            g.counts, oracle_ct(db, point, keep),
            err_msg=f"{policy_cls.__name__} keep={[str(v) for v in keep]}")


def test_complete_ct_many_reads_each_shared_block_once():
    """Families of one point share sub-pattern blocks: the batch asks the
    provider for fewer tables than the queries joined one by one."""
    db = mixed_db()
    point = build_lattice(db.schema, 2)[-1]
    pool = [v for v in point.all_ct_vars(db.schema, include_rind=True)
            if v.kind != "edge"]
    rinds = tuple(v for v in pool if v.kind == "rind")
    attr = next(v for v in pool if v.kind == "attr")
    assert len(rinds) >= 2
    keeps = [(attr, r) for r in rinds] + [(attr,) + rinds]

    class Counting:
        def __init__(self):
            self.inner = OnDemandPositives(
                CountingEngine(db, "sparse", CostStats()))
            self.calls = 0

        def positive(self, p, keep):
            self.calls += 1
            return self.inner.positive(p, keep)

        def hist(self, var, keep):
            self.calls += 1
            return self.inner.hist(var, keep)

    batched, single = Counting(), Counting()
    got = complete_ct_many([(point, k) for k in keeps], batched)
    for keep, g in zip(keeps, got):
        np.testing.assert_array_equal(
            g.counts, complete_ct(point, keep, single).counts)
    assert 0 < batched.calls < single.calls


# ------------------------------------------------------------ strategies ----

@pytest.mark.parametrize("sname,ex", STRAT_X_EXEC)
def test_batched_rounds_match_unbatched_and_oracle(sname, ex):
    """family_ct_many (batched negative phase) == per-family butterfly ==
    per-family blockwise == oracle, on a random schema."""
    db = random_db(0)
    lattice = build_lattice(db.schema, 2)
    point = lattice[-1]
    rng = np.random.default_rng(11)
    keeps = random_keeps(rng, point, db.schema)
    keeps.append(())

    batched = make_strategy(sname, executor=ex)
    batched.prepare(db, lattice)
    got = batched.family_ct_many(point, keeps)

    butterfly = make_strategy(sname, executor=ex)
    butterfly.prepare(db, lattice)
    blockwise = make_strategy(sname, executor=ex, use_butterfly=False)
    blockwise.prepare(db, lattice)
    for keep, g in zip(keeps, got):
        want = oracle_ct(db, point, keep)
        msg = f"{sname}/{ex} keep={[str(v) for v in keep]}"
        np.testing.assert_allclose(np.asarray(g.counts), want, atol=1e-3,
                                   err_msg=msg)
        for ref in (butterfly, blockwise):
            w = ref.family_ct(point, keep)
            assert w.vars == g.vars
            np.testing.assert_allclose(np.asarray(g.counts),
                                       np.asarray(w.counts), atol=1e-3,
                                       err_msg=msg)


@pytest.mark.parametrize("sname,ex", STRAT_X_EXEC)
def test_batched_rounds_card1_domains(sname, ex):
    """Card-1 attribute domains and an empty relationship table through
    the batched negative phase."""
    db = edge_case_db()
    lattice = build_lattice(db.schema, 2)
    point = lattice[-1]
    pool = list(point.all_ct_vars(db.schema, include_rind=True))
    keeps = [tuple(pool), (),
             tuple(v for v in pool if v.kind == "attr"),
             tuple(v for v in pool if v.kind in ("attr", "rind"))]
    st = make_strategy(sname, executor=ex)
    st.prepare(db, lattice)
    got = st.family_ct_many(point, keeps)
    for keep, g in zip(keeps, got):
        want = oracle_ct(db, point, keep)
        np.testing.assert_allclose(
            np.asarray(g.counts), want, atol=1e-3,
            err_msg=f"{sname}/{ex} keep={[str(v) for v in keep]}")
