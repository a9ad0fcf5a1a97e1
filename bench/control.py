"""Readings for the limits of ``correct``: the cell's compared numbers over
many seeds, for the program as its configuration states it, for the
control (the program with its next lower precision switched on), and for
the program with a fault planted in its search.

    python3 bench/control.py --workload vg-discover --seconds 10 \\
        --seeds 1 2 3 --dtype float32 bfloat16
    python3 bench/control.py --workload vg-discover --seconds 10 \\
        --seeds 1 2 3 --dtype float32 --fault stop-climbing

All runs share one process (and its compiled programs).  Each prints one
JSON line: seed, dtype, whether it came out correct, and every compared
number with its limit.  The benchmark's own runs never run this.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def no_moves(climb):
    """The search fault: each climb scores its starting model and takes no
    move."""
    def climb_none(self, point, init_parents=None):
        self.max_moves = 0
        return climb(self, point, init_parents)
    return climb_none


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--dtype", nargs="+", default=["float32", "bfloat16"])
    ap.add_argument("--fault", choices=["stop-climbing"])
    args = ap.parse_args(argv)
    if args.fault == "stop-climbing":
        from repro.core.search import StructureSearch
        StructureSearch.climb_point = no_moves(StructureSearch.climb_point)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.runner import run_cell

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    for dtype in args.dtype:
        for seed in args.seeds:
            out = {"workload": args.workload, "seed": seed, "dtype": dtype,
                   "fault": args.fault}
            try:
                line = run_cell(ROOT, args.workload, seed, args.seconds,
                                False, require_tpu=True,
                                config_overrides={"dtype": dtype}, log=log)
                out.update(correct=line["correct"], checks=line["checks"],
                           metrics=line["metrics"])
            except Exception as e:      # noqa: BLE001 -- a crash is a reading
                traceback.print_exc()
                out.update(correct=False, error=repr(e)[:500])
            print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
