"""Pallas-kernel micro-benchmarks: shape sweeps, correctness vs the jnp
oracle, and us/call timings.

This container is CPU-only, so timings come from two paths:
  * ``interpret=True`` Pallas — correctness of the kernel *body* (what the
    dry-run cannot exercise);
  * the jnp reference — the XLA-compiled roofline stand-in on this host.

Real-TPU timing is out of scope here; the kernels' VMEM/BlockSpec reasoning
is recorded in EXPERIMENTS.md §Perf and the per-kernel headers.
"""

from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Callable, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref


def _time(fn: Callable, *args, reps: int = 5) -> float:
    fn(*args)                              # compile / warm
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6   # us


def bench_hist() -> List[dict]:
    rows = []
    key = jax.random.PRNGKey(1)
    for n, p, d in ((4096, 64, 128), (65536, 256, 128), (262144, 1024, 64)):
        codes = jax.random.randint(key, (n,), 0, p, jnp.int32)
        vals = jax.random.uniform(key, (n, d), jnp.float32)
        want = ref.segment_hist_ref(codes, vals, p)
        got = ops.segment_hist(codes, vals, p, interpret=True)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-2)
        us_ref = _time(lambda c, v: ref.segment_hist_ref(c, v, p), codes, vals)
        # interpret mode executes the kernel body in Python — time it only
        # for shapes where that stays in the seconds range (the big grid is
        # still correctness-checked above)
        us_int = (None if n > 100_000 else round(_time(
            lambda c, v: ops.segment_hist(c, v, p, interpret=True),
            codes, vals, reps=1), 1))
        rows.append({"kernel": "segment_hist", "n": n, "segments": p, "d": d,
                     "us_ref": round(us_ref, 1),
                     "us_interpret": us_int})
    return rows


def bench_segsum() -> List[dict]:
    """The sparse executors' scatter-add hop: Pallas one-hot contraction
    (interpret mode here) vs the ``jax.ops.segment_sum`` reference, over
    edge-count x segment-space shapes spanning leaf hops (``d`` absent,
    weighted ones) and dense-message hops.  Segment spaces mirror the
    flattened ``(parent, code)`` ids the executor actually emits,
    including out-of-range padding."""
    rows = []
    rng = np.random.default_rng(3)
    for n, p, d in ((800, 256, None), (4096, 1024, None),
                    (800, 256, 16), (4096, 2048, 64)):
        # +3: a few ids beyond the segment space, like edge-bucket padding
        seg = jnp.asarray(rng.integers(0, p + 3, size=n, dtype=np.int32))
        if d is None:
            w = jnp.asarray(rng.uniform(0, 2, size=n).astype(np.float32))
            want = ref.ones_segment_sum_ref(seg, w, p)
            got = ops.ones_segment_sum(seg, w, p, interpret=True)
            us_ref = _time(lambda s, v: ref.ones_segment_sum_ref(s, v, p),
                           seg, w)
            us_int = _time(lambda s, v: ops.ones_segment_sum(
                s, v, p, interpret=True), seg, w, reps=1)
        else:
            w = jnp.asarray(rng.uniform(0, 2, size=(n, d)).astype(np.float32))
            want = ref.edge_segment_sum_ref(seg, w, p)
            got = ops.edge_segment_sum(seg, w, p, interpret=True)
            us_ref = _time(lambda s, v: ref.edge_segment_sum_ref(s, v, p),
                           seg, w)
            us_int = _time(lambda s, v: ops.edge_segment_sum(
                s, v, p, interpret=True), seg, w, reps=1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-2)
        rows.append({"kernel": "segment_sum", "n": n, "segments": p,
                     "d": d or 1,
                     "mode": "ones" if d is None else "rows",
                     "us_ref": round(us_ref, 1),
                     "us_interpret": round(us_int, 1)})
    return rows


def bench_bdeu() -> List[dict]:
    rows = []
    key = jax.random.PRNGKey(2)
    for q, r in ((64, 8), (1024, 16), (8192, 4)):
        nijk = jax.random.poisson(key, 3.0, (q, r)).astype(jnp.float32)
        want = ref.bdeu_ref(nijk, 1.0, q, r)
        got = ops.bdeu(nijk, ess=1.0, interpret=True)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-2)
        us_ref = _time(jax.jit(lambda x: ref.bdeu_ref(x, 1.0, q, r)), nijk)
        us_int = _time(lambda x: ops.bdeu(x, ess=1.0, interpret=True), nijk)
        rows.append({"kernel": "bdeu", "q": q, "r": r,
                     "us_ref": round(us_ref, 1),
                     "us_interpret": round(us_int, 1)})
    return rows


def main(out_dir: str = "results/bench",
         bench_json: str = "BENCH_counting.json") -> List[dict]:
    rows = bench_hist() + bench_segsum() + bench_bdeu()
    for r in rows:
        print("[kernels] " + ",".join(f"{k}={v}" for k, v in r.items()),
              flush=True)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "kernels.json").write_text(json.dumps(rows, indent=1))
    print(f"[kernels] wrote {out / 'kernels.json'}")
    # the segment-sum rows also join the cross-PR counting trajectory:
    # they time the executors' innermost hop primitive, so a kernel-side
    # regression shows up next to the serve/flood history it would cause
    if bench_json:
        path = Path(bench_json)
        try:
            history = json.loads(path.read_text()) if path.exists() else []
        except json.JSONDecodeError:
            history = []
        history.extend({"bench": "kernel_segsum", **r} for r in rows
                       if r["kernel"] == "segment_sum")
        path.write_text(json.dumps(history, indent=1))
        print(f"[kernels] appended segment_sum rows to {path}")
    return rows


if __name__ == "__main__":
    main()
