"""Workloads shared by the tests: the family stream a structure search asks
for, and the paper's Table 4 databases at test size."""

import itertools
from typing import List, Tuple

from repro.core.database import RelationalDB, paper_benchmark_db
from repro.core.variables import build_lattice

# name -> (scale, max chain length) for ``paper_benchmark_db``: 300-600
# rows each, small enough for the grounding oracle; every table keeps the
# stand-ins' floor of 8 rows.  Mondial stops at chain length 1: its
# two-relationship point has 16 axes, 4.2e8 positive and 1.7e9 complete
# cells at any scale.
PAPER_TEST_SIZES = {
    "UW": (0.42, 2),
    "Mondial": (0.4, 1),
    "Hepatitis": (0.025, 2),
    "Mutagenesis": (0.03, 2),
    "MovieLens": (0.02, 2),
    "Financial": (0.002, 2),
    "IMDb": (0.0003, 2),
    "VisualGenome": (2.2e-5, 2),
}


def family_workload(db: RelationalDB, lattice, max_parents: int = 3,
                    per_point: int = 400) -> List[Tuple]:
    """Deterministic stream of (point, keep) families, mimicking what
    hill-climbing generates: every child with parent sets of size 0..k,
    round-robin over children, capped per lattice point.  The cap is sized
    so each point sees a realistic search stream (hundreds of families) —
    this is what makes ONDEMAND re-run its JOINs, as in the paper."""
    out: List[Tuple] = []
    for point in lattice:
        nodes = list(point.all_ct_vars(db.schema, include_rind=True))
        fams = []
        for child in nodes:
            others = [v for v in nodes if v != child]
            for k in range(0, max_parents + 1):
                for parents in itertools.combinations(others[:7], k):
                    fams.append((point, tuple(sorted(parents)) + (child,)))
        # interleave children so truncation keeps diversity
        fams.sort(key=lambda f: (len(f[1]), str(f[1][-1])))
        out.extend(fams[:per_point])
    return out


def paper_case(name: str):
    """``(db, lattice)`` for one of the paper's databases at test size."""
    scale, chain = PAPER_TEST_SIZES[name]
    db = paper_benchmark_db(name, seed=0, scale=scale)
    return db, build_lattice(db.schema, chain)


def relational_families(db: RelationalDB, lattice,
                        per_point: int = 4) -> List[Tuple]:
    """The first ``per_point`` families of each lattice point, in
    :func:`family_workload`'s order, that keep an indicator or an edge
    attribute: the axes the Möbius join fills in."""
    out: List[Tuple] = []
    taken = dict.fromkeys(lattice, 0)
    for point, keep in family_workload(db, lattice):
        if taken[point] < per_point and any(v.kind != "attr" for v in keep):
            taken[point] += 1
            out.append((point, keep))
    return out
