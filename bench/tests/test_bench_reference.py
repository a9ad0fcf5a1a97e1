"""The benchmark's generator and plain reference agree with the program
(at tiny sizes, on the CPU): the generator makes the program's own
stand-in data, and the reference's tables equal the program's
brute-force oracle."""

import itertools
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.data.generate import Dataset, Entity, Relation, generate  # noqa
from bench.harness import system  # noqa: E402
from bench.reference import counts as rc  # noqa: E402
from bench.reference.bdeu import bdeu  # noqa: E402

IMDB_TINY = {"prefix": "imdb", "entity_types": 3, "entities_per_type": 30,
             "attrs_per_entity": 3, "attr_card": 3, "relationships": 3,
             "edges": [120, 75, 33], "correlation": 0.7}


def as_dataset(db) -> Dataset:
    ds = Dataset()
    for n, t in db.entities.items():
        ds.entities[n] = Entity(n, t.size, dict(t.attrs),
                                {a.name: a.card for a in t.type.attrs})
    for n, t in db.relations.items():
        ds.relations[n] = Relation(n, t.type.src, t.type.dst, t.src, t.dst,
                                   dict(t.attrs),
                                   {a.name: a.card for a in t.type.attrs})
    return ds


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
def test_generator_is_the_programs_stand_in(seed):
    from repro.core import paper_benchmark_db
    want = paper_benchmark_db("IMDb", seed=seed, scale=0.0005)
    sizes = dict(entities_per_type=want.entities["imdb_e0"].size,
                 edges=[r.num_edges for r in want.relations.values()])
    got = generate(dict(IMDB_TINY, **sizes), seed)
    for name, e in want.entities.items():
        for a, col in e.attrs.items():
            np.testing.assert_array_equal(got.entities[name].attrs[a], col)
    for name, r in want.relations.items():
        g = got.relations[name]
        np.testing.assert_array_equal(g.src, r.src)
        np.testing.assert_array_equal(g.dst, r.dst)
        for a, col in r.attrs.items():
            np.testing.assert_array_equal(g.attrs[a], col)


def test_relabelled_data_has_the_same_counts():
    from repro.core import build_lattice
    spec = dict(IMDB_TINY, base_seed=3)
    a, b = generate(spec, 1), generate(spec, 2 ** 31 + 9)
    assert not np.array_equal(a.relations["imdb_R0"].src,
                              b.relations["imdb_R0"].src)
    schema = system.build_db(a).schema
    for point in build_lattice(schema, 2):
        atoms = system.ref_atoms(point)
        np.testing.assert_array_equal(rc.complete_table(a, atoms).counts,
                                      rc.complete_table(b, atoms).counts)


def _dbs():
    from repro.core.database import synth_db
    from repro.core.schema import Attribute, EntityType, Relationship, Schema
    tiny = system.build_db(generate(dict(IMDB_TINY, entities_per_type=5,
                                         attrs_per_entity=2,
                                         edges=[9, 7, 6]), 5))
    selfrel = synth_db(Schema(
        (EntityType("u", 5, (Attribute("g", 2),)),
         EntityType("p", 3, (Attribute("t", 3),))),
        (Relationship("Fr", "u", "u", ()),
         Relationship("Lk", "u", "p", (Attribute("s", 2),)))),
        {"Fr": 7, "Lk": 6}, seed=1)
    return [tiny, selfrel]


@pytest.mark.parametrize("which", [0, 1])
def test_reference_tables_equal_the_oracle(which):
    from repro.core import build_lattice
    from repro.core.oracle import oracle_ct
    db = _dbs()[which]
    ds = as_dataset(db)
    checked = 0
    for point in build_lattice(db.schema, 2):
        full = rc.complete_table(ds, system.ref_atoms(point))
        pos = rc.positive_table(ds, system.ref_atoms(point))
        nodes = point.all_ct_vars(db.schema, include_rind=True)
        for r in range(4):
            for keep in itertools.combinations(nodes, r):
                axes = [system.ref_axis(v) for v in keep]
                np.testing.assert_array_equal(full.project(axes),
                                              oracle_ct(db, point, keep))
                attrs = [v for v in keep if v.kind != "rind"]
                want = oracle_ct(db, point, attrs, require_positive=True)
                cut = tuple(slice(0, v.card - 1) if v.kind == "edge"
                            else slice(None) for v in attrs)
                got = pos.project([system.ref_axis(v) for v in attrs])
                np.testing.assert_array_equal(got, want[cut])
                checked += 1
    assert checked > 50


def test_reference_bdeu_equals_the_programs_score():
    from repro.core.bdeu import bdeu_score_2d
    rng = np.random.default_rng(0)
    for shape in [(3,), (3, 4), (2, 3, 4), (4, 2, 2, 3)]:
        t = rng.integers(0, 50, size=shape).astype(np.float64)
        want = float(bdeu_score_2d(t.reshape(-1, shape[-1]), ess=1.0))
        assert bdeu(t, len(shape) - 1) == pytest.approx(want, rel=1e-5)
