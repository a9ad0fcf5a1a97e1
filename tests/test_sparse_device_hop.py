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
  nothing, stages no edge column and uploads no edge column;
* leaf hops through the blocked kernel over parent-sorted layouts (forced
  on by ``REPRO_SEGSUM_PALLAS=1``) equal the dense executor and the
  oracle; a rewritten relationship rebuilds its layout, a hub parent
  falls back to scatter, and the sharded executor keeps its flat path.
"""

import gc
import textwrap

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
from tests.test_distributed_counting import _run_subprocess
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


# ------------------------------------------------------- blocked leaf hops ---
def blocked_db(seed=0):
    """Relationships whose parent ends are small enough for a whole tile,
    with more edges than two thirds of a tile's slots: every leaf hop of
    a chain of up to two fits the blocked layout."""
    att = lambda n, c=2: Attribute(n, c)
    schema = Schema(
        entities=(
            EntityType("a", 8, (att("x", 3),)),
            EntityType("b", 200, (att("z", 2),)),
            EntityType("c", 8, (att("w", 2),)),
            EntityType("d", 200, (att("v", 3),)),
        ),
        relationships=(
            Relationship("R1", "a", "b", (att("e1", 2),)),
            Relationship("R2", "b", "c", (att("e2", 3),)),
            Relationship("R3", "c", "d", ()),
        ),
    )
    return synth_db(schema, {"R1": 500, "R2": 500, "R3": 500}, seed=seed)


def _traced_contract(engine, rels):
    """One traced contraction: (table, its ``count.positive`` span)."""
    tab = engine.contract(point_from_rels(engine.db.schema, rels))
    span = [r for r in engine.executor.tracer.records()
            if r.name == "count.positive"][-1]
    return tab, span


@pytest.mark.parametrize("rels", [["R1"], ["R2"], ["R3"], ["R1", "R2"],
                                  ["R2", "R3"], ["R1", "R2", "R3"]],
                         ids="-".join)
def test_blocked_positives_equal_dense_and_oracle(rels, monkeypatch):
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "1")
    db = blocked_db()
    ex = SparseExecutor()
    ex.tracer = Tracer()
    tab, span = _traced_contract(CountingEngine(db, ex), rels)
    plan = compile_plan(db.schema, point_from_rels(db.schema, rels))
    dense = DenseExecutor().positive(db, plan)
    assert tab.vars == dense.vars
    np.testing.assert_array_equal(np.asarray(tab.counts),
                                  np.asarray(dense.counts))
    if len(rels) < 3:           # the grounding oracle: 8 x 200 x 8 at most
        want = oracle_ct(db, plan.point, tab.vars, require_positive=True)
        np.testing.assert_array_equal(np.asarray(tab.counts), want)
    # leaf hops run blocked; the path of three's dense-message hop scatters
    dense_hops = _dense_message_hops(plan)
    assert span.attrs["blocked_hops"] == plan.hops - dense_hops
    assert span.attrs["scattered_hops"] == dense_hops
    assert span.attrs["edges_uploaded"] == plan.hops


def test_a_rewritten_relationship_rebuilds_its_layout(monkeypatch):
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "1")
    db = blocked_db(1)
    ex = SparseExecutor()
    ex.tracer = Tracer()
    eng = CountingEngine(db, ex)
    for _ in range(2):
        _, span = _traced_contract(eng, ["R1"])
    assert span.attrs["blocked_hops"] == span.attrs["edges_resident"] == 1
    # the layout stands in for the edge columns: only entity columns
    # have device copies
    assert len(ex._layouts) == 1
    assert set(ex._mirrors._live) == {(id(db.entities["a"].attrs["x"]),),
                                      (id(db.entities["b"].attrs["z"]),)}
    db.insert_facts("R1", *fresh_pairs(db, "R1", 4,
                                       np.random.default_rng(7)),
                    {"e1": [0, 1, 1, 0]})
    gc.collect()
    assert len(ex._layouts) == 0
    tab, span = _traced_contract(eng, ["R1"])
    assert span.attrs["blocked_hops"] == span.attrs["edges_uploaded"] == 1
    assert len(ex._layouts) == 1
    want = oracle_ct(db, point_from_rels(db.schema, ["R1"]), tab.vars,
                     require_positive=True)
    np.testing.assert_array_equal(np.asarray(tab.counts), want)


def test_a_hub_parent_falls_back_to_scatter(monkeypatch):
    """Two tiles of parents: 800 spread edges fit the layout; a parent
    joined to all 1024 children makes its tile's slots, and so every
    tile's, more than 1.5 times the edges."""
    monkeypatch.setenv("REPRO_SEGSUM_PALLAS", "1")
    schema = Schema(
        entities=(EntityType("u", 512, (Attribute("x", 2),)),
                  EntityType("v", 1024, (Attribute("y", 3),))),
        relationships=(Relationship("H", "u", "v", (Attribute("e", 2),)),))
    db = synth_db(schema, {"H": 800}, seed=3)
    ex = SparseExecutor()
    ex.tracer = Tracer()
    eng = CountingEngine(db, ex)
    plan = compile_plan(db.schema, point_from_rels(db.schema, ["H"]))
    assert plan.root.var.etype == "u"
    _, span = _traced_contract(eng, ["H"])
    assert (span.attrs["blocked_hops"], span.attrs["scattered_hops"]) == (1, 0)

    rt = db.relations["H"]
    taken = set(rt.dst[rt.src == 0].tolist())
    hub = np.array([j for j in range(1024) if j not in taken], np.int32)
    db.insert_facts("H", np.zeros_like(hub), hub,
                    {"e": (hub % 2).astype(np.int32)})
    tab, span = _traced_contract(eng, ["H"])
    assert (span.attrs["blocked_hops"], span.attrs["scattered_hops"]) == (0, 1)
    np.testing.assert_array_equal(
        np.asarray(tab.counts),
        np.asarray(DenseExecutor().positive(db, plan).counts))


SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ["REPRO_SEGSUM_PALLAS"] = "1"
    import jax, numpy as np
    from repro.core import CountingEngine, point_from_rels
    from repro.core.distributed import ShardedSparseExecutor
    from repro.core.executors import SparseExecutor
    from repro.obs import Tracer
    from tests.test_sparse_device_hop import blocked_db, _traced_contract

    db = blocked_db()
    mesh = jax.make_mesh((8,), ("data",))
    for ex in (ShardedSparseExecutor(mesh=mesh, axis="data"),
               SparseExecutor()):
        ex.tracer = Tracer()
        tab, span = _traced_contract(CountingEngine(db, ex), ["R1", "R2"])
        counts = np.asarray(tab.counts)
        if isinstance(ex, ShardedSparseExecutor):
            assert ex.n_ranks == 8
            assert (span.attrs["blocked_hops"],
                    span.attrs["scattered_hops"]) == (0, 2), span.attrs
            with ex.local_mode():          # one device: blocked again
                local = ex.positive(db, CountingEngine(db, ex).plan(
                    point_from_rels(db.schema, ["R1", "R2"])))
            assert ex.blocked_hops == 2
            np.testing.assert_array_equal(np.asarray(local.counts), counts)
            sharded = counts
        else:
            assert span.attrs["blocked_hops"] == 2
            np.testing.assert_array_equal(counts, sharded)
    print("SHARDED-FLAT-OK")
""")


def test_the_sharded_executor_keeps_its_flat_path():
    """On 8 virtual CPU devices a sharded hop scatters flat ids over the
    mesh even where a single device would run the blocked kernel."""
    assert "SHARDED-FLAT-OK" in _run_subprocess(SHARDED_SCRIPT)
