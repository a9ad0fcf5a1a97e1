import os
if os.environ.get("REPRO_DRYRUN") == "1":          # before any jax import
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Distributed statistical-relational model discovery (the paper's workload).

Two modes:

* default — run end-to-end discovery (lattice -> HYBRID counting -> BDeu
  hill-climb) on the LOCAL mesh with the edge tables sharded over ``data``
  (``core/distributed.py``); prints the learned model + counting stats.

      PYTHONPATH=src python -m repro.launch.discover --db IMDb --scale 0.1

* --dryrun (env REPRO_DRYRUN=1) — lower + compile the sharded JOIN-sweep hop
  (the positive ct-table contraction, the JOIN-problem kernel the paper
  pre-counts) for a Visual-Genome-scale edge table on the production mesh,
  and report the three roofline terms.  This is the §Perf H3 mesh cell.

      REPRO_DRYRUN=1 PYTHONPATH=src python -m repro.launch.discover \
          --dryrun --edges 15833273 --entities 200000 --dvals 48
"""

import argparse
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.database import PAPER_DATASETS, paper_benchmark_db
from repro.core.distributed import sharded_positive_ct, _sharded_hop
from repro.core.search import discover_model
from repro.core.strategies import make_strategy
from repro.hlo_analysis import analyze as analyze_hlo
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.roofline import roofline_terms


def run_local(db_name: str, scale: float) -> None:
    db = paper_benchmark_db(db_name, scale=scale)
    mesh = make_local_mesh()
    print(f"database {db_name} (scale {scale}): {db.total_rows} rows; "
          f"mesh {dict(mesh.shape)}")
    # distributed JOIN sweep for every lattice point, then standard HYBRID
    from repro.core.variables import build_lattice
    lattice = build_lattice(db.schema, 2)
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


def run_dryrun(edges: int, entities: int, dvals: int, multi_pod: bool,
               out_dir: str) -> dict:
    """Lower the sharded join hop: (child one-hot msgs over `entities` rows)
    gathered through `edges` edge rows, expanded by a card-4 edge attribute,
    segment-summed to parents, psum over data.  Shapes are VG-scale."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    axis = "data"
    nsh = mesh.shape[axis]
    pad = ((edges + nsh - 1) // nsh) * nsh
    v_axis = "model" if dvals % mesh.shape["model"] == 0 else None
    hop = _sharded_hop(mesh, axis, entities, 1, jnp.float32,
                       value_axis=v_axis)

    cm = jax.ShapeDtypeStruct((entities, dvals), jnp.float32)
    gi = jax.ShapeDtypeStruct((pad,), jnp.int32)
    si = jax.ShapeDtypeStruct((pad,), jnp.int32)
    w = jax.ShapeDtypeStruct((pad,), jnp.float32)
    hot = jax.ShapeDtypeStruct((pad, 5), jnp.float32)

    with jax.sharding.set_mesh(mesh):
        lowered = jax.jit(hop).lower(cm, gi, si, w, hot)
        compiled = lowered.compile()
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    totals = analyze_hlo(hlo)
    terms = roofline_terms(
        {"flops": totals["flops"], "bytes accessed": totals["bytes"]},
        {"all": {"link_bytes": totals["coll_link_bytes"], "count": 0,
                 "bytes": totals["coll_link_bytes"]}},
        mesh.size)
    rec = {
        "cell": "counting-join-sweep",
        "edges": edges, "entities": entities, "dvals": dvals,
        "mesh": "pod2x16x16" if multi_pod else "pod16x16",
        "chips": mesh.size,
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "roofline": terms,
    }
    print(json.dumps(rec, indent=1, default=str))
    if out_dir:
        p = Path(out_dir)
        p.mkdir(parents=True, exist_ok=True)
        (p / f"counting__{rec['mesh']}.json").write_text(
            json.dumps(rec, indent=1, default=str))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--db", choices=PAPER_DATASETS, default="UW")
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--edges", type=int, default=15_833_273)
    ap.add_argument("--entities", type=int, default=200_000)
    ap.add_argument("--dvals", type=int, default=48)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    enable_compile_cache()
    if args.dryrun:
        if os.environ.get("REPRO_DRYRUN") != "1":
            print("set REPRO_DRYRUN=1 (before python starts) for --dryrun",
                  file=sys.stderr)
            return 2
        run_dryrun(args.edges, args.entities, args.dvals, args.multi_pod,
                   args.out)
    else:
        run_local(args.db, args.scale)
    return 0


if __name__ == "__main__":
    sys.exit(main())
