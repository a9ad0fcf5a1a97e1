"""Seeded synthetic deployments: the paper's Table 4 stand-ins.

A copy of the stand-in generator (a generic connected schema plus edges
with planted attribute correlations), kept with the benchmark so that a
change to the program cannot move the data a cell runs on.  Output is
plain NumPy: the harness turns it into the system's own database object,
and the reference reads it directly.

A configuration file's ``schema`` block fixes the shape:

    {"prefix": "vg", "entity_types": 4, "entities_per_type": 200000,
     "attrs_per_entity": 1, "attr_card": 3, "relationships": 8,
     "edges": [1900000, ...], "correlation": 0.7, "base_seed": 7100000001}

Relationship ``r`` runs from entity type ``r % n`` to ``(r + 1) % n``;
each has one edge attribute whose value copies ``(src attr0 + dst attr0)
mod card`` with probability ``correlation`` and is uniform otherwise.

With ``base_seed`` the data is one fixed draw from that seed, and a run's
seed only relabels it (:func:`relabel`): every seed then asks the system
for the same counts, the same search and the same programs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np


@dataclass
class Entity:
    name: str
    size: int
    attrs: Dict[str, np.ndarray]          # name -> int32[size]
    cards: Dict[str, int]


@dataclass
class Relation:
    name: str
    src_type: str
    dst_type: str
    src: np.ndarray                       # int32[m]
    dst: np.ndarray                       # int32[m]
    attrs: Dict[str, np.ndarray]          # name -> int32[m]
    cards: Dict[str, int]

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])


@dataclass
class Dataset:
    entities: Dict[str, Entity] = field(default_factory=dict)
    relations: Dict[str, Relation] = field(default_factory=dict)

    @property
    def total_rows(self) -> int:
        return (sum(e.size for e in self.entities.values())
                + sum(r.num_edges for r in self.relations.values()))


def schema_layout(spec: dict) -> Tuple[List[tuple], List[tuple]]:
    """Entity types ``(name, size, [(attr, card)])`` and relationships
    ``(name, src type, dst type, [(attr, card)])`` of a schema block."""
    pre, n_ent = spec["prefix"], int(spec["entity_types"])
    card = int(spec["attr_card"])
    ents = [(f"{pre}_e{i}", int(spec["entities_per_type"]),
             [(f"a{i}_{j}", card) for j in range(int(spec["attrs_per_entity"]))])
            for i in range(n_ent)]
    rels = []
    for r in range(int(spec["relationships"])):
        s, d = r % n_ent, (r + 1) % n_ent
        if s == d:
            d = (d + 1) % n_ent
        rels.append((f"{pre}_R{r}", ents[s][0], ents[d][0],
                     [(f"r{r}_a0", card)]))
    return ents, rels


def generate(spec: dict, seed: int) -> Dataset:
    """The deployment's data from ``seed``: same seed, same data."""
    if "base_seed" in spec:
        return relabel(draw(spec, int(spec["base_seed"])), seed)
    return draw(spec, seed)


def relabel(data: Dataset, seed: int) -> Dataset:
    """The same data under new labels from ``seed``: entity ids permuted
    within each type, and each relationship's edges listed in a new order.
    Every count over attributes and relationship indicators is unchanged."""
    rng = np.random.default_rng(seed)
    out, new_id = Dataset(), {}
    for name, e in data.entities.items():
        new_id[name] = rng.permutation(e.size).astype(np.int32)
        at = np.empty(e.size, np.int64)        # new id -> old id
        at[new_id[name]] = np.arange(e.size)
        out.entities[name] = Entity(name, e.size,
                                    {a: c[at] for a, c in e.attrs.items()},
                                    dict(e.cards))
    for name, r in data.relations.items():
        order = rng.permutation(r.num_edges)
        out.relations[name] = Relation(
            name, r.src_type, r.dst_type, new_id[r.src_type][r.src[order]],
            new_id[r.dst_type][r.dst[order]],
            {a: c[order] for a, c in r.attrs.items()}, dict(r.cards))
    return out


def draw(spec: dict, seed: int) -> Dataset:
    """One draw of the deployment's data from ``seed``."""
    ents, rels = schema_layout(spec)
    edges = [int(m) for m in spec["edges"]]
    if len(edges) != len(rels):
        raise ValueError(f"{len(edges)} edge counts for {len(rels)} "
                         f"relationships")
    correlation = float(spec["correlation"])
    rng = np.random.default_rng(seed)
    out = Dataset()
    for name, size, attrs in ents:
        out.entities[name] = Entity(
            name, size,
            {a: rng.integers(0, c, size=size, dtype=np.int32)
             for a, c in attrs},
            dict(attrs))
    for (name, st, dt, attrs), m in zip(rels, edges):
        ns, nd = out.entities[st].size, out.entities[dt].size
        # unique (src, dst) pairs: a relationship is a set of pairs
        over = rng.integers(0, ns * nd, size=min(int(m * 1.3) + 8, ns * nd),
                            dtype=np.int64)
        over = np.unique(over)
        rng.shuffle(over)
        over = over[:m]
        src = (over // nd).astype(np.int32)
        dst = (over % nd).astype(np.int32)
        if st == dt:
            keep = src != dst
            src, dst = src[keep], dst[keep]
        m = src.shape[0]
        se, de = out.entities[st], out.entities[dt]
        s_anchor = (se.attrs[next(iter(se.attrs))][src] if se.attrs
                    else np.zeros(m, np.int32))
        d_anchor = (de.attrs[next(iter(de.attrs))][dst] if de.attrs
                    else np.zeros(m, np.int32))
        cols = {}
        for a, c in attrs:
            noise = rng.integers(0, c, size=m, dtype=np.int32)
            signal = ((s_anchor + d_anchor) % c).astype(np.int32)
            pick = rng.random(m) < correlation
            cols[a] = np.where(pick, signal, noise).astype(np.int32)
        out.relations[name] = Relation(name, st, dt, src, dst, cols,
                                       dict(attrs))
    return out
