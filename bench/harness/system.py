"""The system under test, built from generated data.

The only module of the harness that imports the program (``repro``): it
turns the benchmark's NumPy data into the program's database object,
builds the configuration's counting stack, and translates between the
program's table axes and the reference's plain axis tuples.
"""

from __future__ import annotations

from typing import List

import numpy as np

from ..reference.counts import Atom as RefAtom


def build_db(data):
    from repro.core.database import (EntityTable, RelationTable,
                                     RelationalDB)
    from repro.core.schema import (Attribute, EntityType, Relationship,
                                   Schema)
    ents = tuple(EntityType(e.name, e.size,
                            tuple(Attribute(a, e.cards[a]) for a in e.attrs))
                 for e in data.entities.values())
    rels = tuple(Relationship(r.name, r.src_type, r.dst_type,
                              tuple(Attribute(a, r.cards[a])
                                    for a in r.attrs))
                 for r in data.relations.values())
    schema = Schema(ents, rels)
    db = RelationalDB(
        schema,
        {e.name: EntityTable(et, dict(e.attrs))
         for e, et in zip(data.entities.values(), ents)},
        {r.name: RelationTable(rt, r.src.copy(), r.dst.copy(),
                               {a: c.copy() for a, c in r.attrs.items()})
         for r, rt in zip(data.relations.values(), rels)})
    db.validate()
    return db


def dtype_of(config: dict):
    import jax.numpy as jnp
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[config.get("dtype", "float32")]


def make_executor(config: dict):
    from repro.core.executors import make_executor as mk
    return mk(config["executor"], dtype=dtype_of(config))


def make_strategy(config: dict, executor):
    from repro.core import make_strategy as mk
    return mk(config["strategy"], executor=executor,
              cache_budget_bytes=int(config["cache_budget_bytes"]),
              dtype=dtype_of(config))


def lattice(db, max_chain_length: int):
    from repro.core import build_lattice
    return build_lattice(db.schema, max_chain_length)


def ref_atoms(point) -> List[RefAtom]:
    return [RefAtom(a.rel, (a.src.etype, a.src.copy),
                    (a.dst.etype, a.dst.copy)) for a in point.atoms]


def ref_axis(cv) -> tuple:
    if cv.kind == "attr":
        var, name = cv.owner
        return ("attr", (var.etype, var.copy), name)
    if cv.kind == "edge":
        return ("edge",) + tuple(cv.owner)
    return ("rind", cv.owner[0])


def table_array(tab) -> np.ndarray:
    """A table's counts on the host (a program table, or counts already
    brought over)."""
    counts = tab if isinstance(tab, np.ndarray) else tab.counts
    return np.asarray(counts, dtype=np.float64)
