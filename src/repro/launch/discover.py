"""Distributed statistical-relational model discovery (the paper's workload).

Runs end-to-end discovery (lattice -> HYBRID counting -> BDeu hill-climb)
on the LOCAL mesh with the edge tables sharded over ``data``
(``core/distributed.py``); prints the learned model + counting stats.

    PYTHONPATH=src python -m repro.launch.discover --db IMDb --scale 0.1
"""

import argparse
import sys

import jax

from repro.core.database import PAPER_DATASETS, paper_benchmark_db
from repro.core.search import discover_model
from repro.core.strategies import make_strategy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh


def run_local(db_name: str, scale: float) -> None:
    db = paper_benchmark_db(db_name, scale=scale)
    mesh = make_local_mesh()
    print(f"database {db_name} (scale {scale}): {db.total_rows} rows; "
          f"mesh {dict(mesh.shape)}")
    strat = make_strategy("HYBRID")
    with jax.sharding.set_mesh(mesh):
        models, strat = discover_model(db, strat, max_chain_length=2,
                                       max_parents=2)
    st = strat.stats.as_dict()
    for point, model in models.items():
        print(f"  [{','.join(sorted(point.rels))}] score={model.score:.1f} "
              f"edges={len(model.edges())}")
    print({k: round(v, 3) if isinstance(v, float) else v
           for k, v in st.items()})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--db", choices=PAPER_DATASETS, default="UW")
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args()
    enable_compile_cache()
    run_local(args.db, args.scale)
    return 0


if __name__ == "__main__":
    sys.exit(main())
