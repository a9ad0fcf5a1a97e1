"""The sparse hop on the device: segment ids built there from edge columns
kept there.

* positives of the sparse executor equal the dense executor's and the
  brute-force oracle's, at chain lengths 1 and 2 and through dense-message
  hops, with child codes gathered by elements and by rows;
* writes between contractions: the written relationship's edge columns
  are uploaded again, the others are reused, and counts follow the store;
* a delta view's device copies die with its arrays, and a store that
  shares an entity table reads the table's last write;
* a second pre-count on a new store version with unchanged data compiles
  nothing, stages no edge column and uploads no edge column.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Attribute, CountingEngine, EntityType, Relationship,
                        Schema, build_lattice, make_strategy,
                        point_from_rels, synth_db)
from repro.core import executors
from repro.core.executors import (_ROW_CHUNK, DenseExecutor, SparseExecutor,
                                  _take_by_rows)
from repro.core.oracle import oracle_ct
from repro.core.plan import compile_plan
from repro.obs import Tracer
from tests.test_mutations import fresh_pairs


def path_db(seed=0):
    """Four entity types on a path a-b-c-d, one edge attribute per
    relationship, and sizes that no two kinds of column share."""
    att = lambda n, c=2: Attribute(n, c)
    schema = Schema(
        entities=(
            EntityType("a", 5, (att("x", 2), att("y", 3))),
            EntityType("b", 4, (att("z", 2),)),
            EntityType("c", 4, (att("w", 3),)),
            EntityType("d", 3, (att("v", 2),)),
        ),
        relationships=(
            Relationship("R1", "a", "b", (att("e1", 2),)),
            Relationship("R2", "b", "c", (att("e2", 3),)),
            Relationship("R3", "c", "d", ()),
        ),
    )
    return synth_db(schema, {"R1": 9, "R2": 8, "R3": 7}, seed=seed)


POINTS = [["R1"], ["R2"], ["R3"], ["R1", "R2"], ["R2", "R3"],
          ["R1", "R2", "R3"]]


def _dense_message_hops(plan):
    """Hops whose child sends an aggregated (dense) message."""
    def node(n):
        out = 0
        for h in n.hops:
            out += (not h.is_leaf_hop) + node(h.child_node)
        return out
    return node(plan.root)


@pytest.mark.parametrize("by_rows", [False, True], ids=["elements", "rows"])
@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("rels", POINTS, ids="-".join)
def test_sparse_positives_equal_dense_and_oracle(rels, seed, by_rows,
                                                 monkeypatch):
    monkeypatch.setattr(executors, "_gathers_by_rows", lambda: by_rows)
    db = path_db(seed)
    point = point_from_rels(db.schema, rels)
    plan = compile_plan(db.schema, point)
    want = oracle_ct(db, point, plan.keep, require_positive=True)
    sparse = SparseExecutor().positive(db, plan)
    dense = DenseExecutor().positive(db, plan)
    assert sparse.vars == dense.vars == plan.keep
    np.testing.assert_array_equal(np.asarray(sparse.counts), want)
    np.testing.assert_array_equal(np.asarray(dense.counts), want)


@pytest.mark.parametrize("n", [0, 5, 1000, _ROW_CHUNK + 1234])
def test_a_gather_by_rows_equals_an_element_gather(n):
    """Codes fetched by rows, from a table whose length is no multiple of
    a row, in one step and in several."""
    rng = np.random.default_rng(n)
    code = rng.integers(0, 50, 1077).astype(np.int32)
    idx = rng.integers(0, code.size, n).astype(np.int32)
    got = jax.jit(_take_by_rows)(jnp.asarray(code), jnp.asarray(idx))
    np.testing.assert_array_equal(np.asarray(got), code[idx])


def test_the_path_of_three_has_a_dense_message_hop():
    db = path_db()
    plan = compile_plan(db.schema, point_from_rels(db.schema, POINTS[-1]))
    assert _dense_message_hops(plan) == 1


def _contract(engine, rels):
    """One traced contraction: (counts, edges_resident, edges_uploaded)."""
    tracer = engine.executor.tracer
    point = point_from_rels(engine.db.schema, rels)
    tab = engine.contract(point)
    span = [r for r in tracer.records() if r.name == "count.positive"][-1]
    want = oracle_ct(engine.db, point, tab.vars, require_positive=True)
    np.testing.assert_array_equal(np.asarray(tab.counts), want)
    return span.attrs["edges_resident"], span.attrs["edges_uploaded"]


def test_writes_upload_only_the_written_relationship():
    db = path_db(2)
    ex = SparseExecutor()
    ex.tracer = Tracer()
    eng = CountingEngine(db, ex)
    rng = np.random.default_rng(0)
    assert _contract(eng, ["R1"]) == (0, 1)
    assert _contract(eng, ["R2"]) == (0, 1)
    assert _contract(eng, ["R1", "R2"]) == (2, 0)

    src, dst = fresh_pairs(db, "R1", 2, rng)
    db.insert_facts("R1", src, dst, {"e1": [1, 0]})
    assert _contract(eng, ["R1", "R2"]) == (1, 1)
    assert _contract(eng, ["R1"]) == (1, 0)

    rt = db.relations["R2"]
    db.delete_facts("R2", rt.src[:2], rt.dst[:2])
    assert _contract(eng, ["R2"]) == (0, 1)
    assert _contract(eng, ["R1", "R2"]) == (2, 0)

    # entity attributes are written in place: the counts follow them, and
    # no edge column moves
    db.update_attrs("b", [0, 3], {"z": [1, 1]})
    db.update_attrs("a", [1], {"y": [2]})
    assert _contract(eng, ["R1", "R2"]) == (2, 0)
    assert _contract(eng, ["R2", "R3"]) == (1, 1)
    assert _contract(eng, ["R1", "R2", "R3"]) == (3, 0)


def test_a_delta_views_copies_die_with_it():
    db = path_db(3)
    ex = SparseExecutor()
    plan = compile_plan(db.schema, point_from_rels(db.schema, ["R1"]))
    delta = db.insert_facts("R1", *fresh_pairs(db, "R1", 3,
                                               np.random.default_rng(1)),
                            {"e1": [0, 1, 1]})
    ex.positive(db, plan)
    held = len(ex._mirrors)
    view = delta.as_db(db)
    got = ex.positive(view, plan)
    want = oracle_ct(view, plan.point, plan.keep, require_positive=True)
    np.testing.assert_array_equal(np.asarray(got.counts), want)
    # the view's src, dst and edge attribute
    assert len(ex._mirrors) == held + 3
    del view, delta, got
    gc.collect()
    assert len(ex._mirrors) == held


def test_a_store_sharing_the_entity_table_reads_its_last_write():
    """A delta view stands at the version its base had when it was made;
    an attribute write through the base afterwards moves the base on but
    changes the shared entity table under both."""
    db = path_db(6)
    ex = SparseExecutor()
    plan = compile_plan(db.schema, point_from_rels(db.schema, ["R1"]))
    delta = db.insert_facts("R1", *fresh_pairs(db, "R1", 3,
                                               np.random.default_rng(3)),
                            {"e1": [1, 0, 1]})
    view = delta.as_db(db)
    ex.positive(db, plan)                  # copies made at the view's version
    db.update_attrs("a", [0, 1, 2, 3, 4], {"x": [1, 0, 1, 0, 1]})
    db.update_attrs("b", [0, 1, 2, 3], {"z": [1, 1, 0, 0]})
    assert view.version == db.version - 2
    for store in (view, db):
        got = ex.positive(store, plan)
        want = oracle_ct(store, plan.point, plan.keep, require_positive=True)
        np.testing.assert_array_equal(np.asarray(got.counts), want)


def test_a_rewritten_relationship_drops_its_old_copies():
    db = path_db(4)
    ex = SparseExecutor()
    plan = compile_plan(db.schema, point_from_rels(db.schema, ["R3"]))
    ex.positive(db, plan)
    held = len(ex._mirrors)
    db.insert_facts("R3", *fresh_pairs(db, "R3", 1,
                                       np.random.default_rng(2)))
    gc.collect()
    assert len(ex._mirrors) == held - 2        # R3's old src and dst
    ex.positive(db, plan)
    assert len(ex._mirrors) == held


class _Compiles:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def __call__(self, event, duration, **kwargs):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)
        return False


def test_a_second_precount_on_a_new_version_moves_no_edge_column():
    db = path_db(5)
    ex = SparseExecutor()
    lattice = build_lattice(db.schema, 2)
    edge_bytes = {int(np.asarray(col).nbytes) for rt in db.relations.values()
                  for col in (rt.src, rt.dst, *rt.attrs.values())}
    entity_bytes = {int(np.asarray(col).nbytes)
                    for et in db.entities.values()
                    for col in et.attrs.values()}
    assert not edge_bytes & entity_bytes
    make_strategy("HYBRID", executor=ex).prepare(db, lattice)

    ex.tracer = tracer = Tracer()
    db.version += 1
    with _Compiles() as compiles:
        strat = make_strategy("HYBRID", executor=ex)
        strat.prepare(db, lattice)
    recs = tracer.records()
    assert compiles.n == 0
    staged = [r.attrs["nbytes"] for r in recs if r.name == "host.stage"]
    assert staged and set(staged) <= entity_bytes
    pos = [r for r in recs if r.name == "count.positive"]
    assert sum(r.attrs["tables"] for r in pos) == len(lattice)
    assert sum(r.attrs["edges_uploaded"] for r in pos) == 0
    assert (sum(r.attrs["edges_resident"] for r in pos)
            == sum(r.attrs["hops"] for r in pos))


@pytest.mark.parametrize("stacked", [False, True])
def test_a_segment_space_past_int32_raises(stacked):
    """2**20 parents times a child code space of 16**4 = 2**16."""
    wide = tuple(Attribute(f"a{i}", 16) for i in range(4))
    schema = Schema(entities=(EntityType("u", 1 << 20, wide),),
                    relationships=(Relationship("F", "u", "u", ()),))
    db = synth_db(schema, {"F": 16})
    plan = compile_plan(db.schema, point_from_rels(db.schema, ["F"]))
    ex = SparseExecutor()
    with pytest.raises(OverflowError, match="exceeds int32"):
        if stacked:
            ex.positive_batch(db, [plan, plan])
        else:
            ex.positive(db, plan)
