"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--suite ...]``.

Suites (one per paper artefact + the perf report):
  counting — paper Fig. 3 (time decomposition), Fig. 4 (peak memory),
             Table 5 (ct sizes), via benchmarks.bench_counting
  kernels  — Pallas kernel shape sweeps vs jnp oracles
  roofline — re-summarise results/dryrun into the §Roofline table

Everything prints to stdout and writes JSON under results/bench/.
"""

from __future__ import annotations

import argparse
import json
from pathlib import Path


def roofline_summary(dryrun_dir: str = "results/dryrun",
                     out_dir: str = "results/bench") -> list:
    rows = []
    for f in sorted(Path(dryrun_dir).glob("*.json")):
        rec = json.loads(f.read_text())
        if not rec.get("ok") or rec.get("tag"):
            continue
        t = rec["roofline"]
        bound = max(t["t_compute_s"], t["t_memory_s"], t["t_collective_s"])
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "compute_s": round(t["t_compute_s"], 6),
            "memory_s": round(t["t_memory_s"], 6),
            "collective_s": round(t["t_collective_s"], 6),
            "bottleneck": t["bottleneck"],
            "roofline_frac": round(t["t_compute_s"] / bound, 4) if bound else None,
            "useful_flops_ratio": round(rec["useful_flops_ratio"], 3),
        })
    for r in rows:
        print("[roofline] " + ",".join(f"{k}={v}" for k, v in r.items()),
              flush=True)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "roofline.json").write_text(json.dumps(rows, indent=1))
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=["counting", "kernels", "roofline",
                                        "all"], default="all")
    ap.add_argument("--scale", type=float, default=None,
                    help="extra multiplier on the per-dataset scales")
    ap.add_argument("--budget", type=float, default=180.0,
                    help="per-(dataset,strategy) soft time budget, seconds")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.suite in ("kernels", "all"):
        from benchmarks import bench_kernels
        bench_kernels.main(out_dir=args.out)
    if args.suite in ("counting", "all"):
        from benchmarks import bench_counting
        bench_counting.main(out_dir=args.out, scale=args.scale,
                            budget_s=args.budget)
    if args.suite in ("roofline", "all"):
        roofline_summary(out_dir=args.out)


if __name__ == "__main__":
    main()
