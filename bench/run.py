"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix and metrics are found by name
from BENCHMARK.json.  Progress and each compared number beside its limit
go to standard error; the last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``).  Without a TPU, or
with fewer chips than the cell asks for, it prints no result and exits 3.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.device import NoChip
    from bench.harness.runner import run_cell

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        line = run_cell(ROOT, args.workload, args.seed, args.seconds,
                        bool(args.trace), require_tpu=True,
                        t_process=T_PROCESS, log=log)
    except NoChip as e:
        log(f"[bench] {e}")
        return 3
    for text in line.pop("_check_lines"):
        log(text)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
