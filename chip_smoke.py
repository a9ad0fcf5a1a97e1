"""Bring-up check of the counting system on a TPU, through its user entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded executor over four chips

One chip:

1. Device: a TPU backs JAX, the Pallas kernels lower natively, and neither
   debug override (``REPRO_PALLAS_INTERPRET``, ``REPRO_SEGSUM_PALLAS``) is
   set.
2. Served discovery at the paper's largest scale: VisualGenome at scale 1.0
   (16 M rows) is pre-counted by HYBRID on the sparse executor under a
   64 MiB cache budget, then searched through
   ``CountingService(...).discovery().discover()``; the same service then
   answers count and complete-CT queries.  Positive tables must equal a host
   NumPy ``bincount`` of the same edges; each complete table must sum to the
   product of its entity counts, with its R=T slice equal to the positive
   table.
3. Native kernels on the path: on databases whose segment spaces fit the
   Pallas segment-sum kernels, all four strategies run on them, against
   the brute-force oracle (tiny database) and the XLA ``segment_sum``
   route (Hepatitis); every kernel call must have been native, and
   lowering it must give a ``tpu_custom_call``.  (The Möbius join runs on
   the host in float64, so no device kernel serves it.)

``--chips 4`` runs only the device check and ``ShardedSparseExecutor`` over a
four-chip ``data`` mesh on VisualGenome, against ``SparseExecutor`` on one
chip.

Progress goes to standard output; its last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``,
printed only when every phase passed.  Any failure exits non-zero.  The
script runs in one process and starts none.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

DEBUG_OVERRIDES = ("REPRO_PALLAS_INTERPRET", "REPRO_SEGSUM_PALLAS")
VG_CACHE_BUDGET = 64 << 20     # the cache budget of bench/configs/vg-full.json
COMPLETE_RTOL = 1e-6           # complete cells reach 4e10: f32 rounding
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def log(msg: str) -> None:
    print(f"[chip-smoke] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts backend compilations, and their seconds, as JAX reports
    them."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.n += 1
            self.seconds += duration

    def since(self, mark=(0, 0.0)) -> str:
        n, seconds = mark
        return f"{self.n - n} compiles ({self.seconds - seconds:.3f} s)"

    def mark(self):
        return self.n, self.seconds


def assert_counts_equal(got, want, k: int, what: str) -> None:
    """Equal count tables.  f32 holds counts exactly up to 2**24; above
    that, two Möbius evaluation orders over ``k`` indicator axes may each
    round, so cells may differ by up to 2**k steps of f32 rounding at the
    table's largest cell."""
    import numpy as np
    top = float(np.max(np.abs(want))) if want.size else 0.0
    tol = 0.0 if top < 2 ** 24 else 2 ** k * np.finfo(np.float32).eps * top
    diff = float(np.max(np.abs(got - want))) if want.size else 0.0
    check(diff <= tol, f"{what}: max |diff| {diff} over tolerance {tol}")


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# -- phase 1: device ---------------------------------------------------------

def device_phase(n_chips: int):
    for name in DEBUG_OVERRIDES:
        check(name not in os.environ,
              f"{name} is set; it is a debug override and must not be")
    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"JAX found no TPU (platform {devices[0].platform!r})")
    check(len(devices) >= n_chips,
          f"{n_chips} chips asked for, {len(devices)} visible")
    from repro.kernels import ops
    check(ops.default_interpret() is False,
          "the Pallas kernels would run in interpret mode")
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"(jax {jax.__version__})")
    return devices


# -- phase 2: served discovery at VisualGenome scale -------------------------

def bincount_positive(db, point, tab):
    """The positive table of a one-relationship point from its raw edges:
    one mixed-radix code per edge, counted with ``np.bincount``."""
    import numpy as np
    (atom,) = point.atoms
    rt = db.relations[atom.rel]
    ends = {atom.src: rt.src, atom.dst: rt.dst}
    code = np.zeros(rt.num_edges, dtype=np.int64)
    for v in tab.vars:
        if v.kind == "attr":
            var, name = v.owner
            col = db.entities[var.etype].attrs[name][ends[var]]
        else:
            col = rt.attrs[v.owner[1]]
        code = code * v.card + col.astype(np.int64)
    size = int(np.prod([v.card for v in tab.vars]))
    return np.bincount(code, minlength=size).reshape(
        tuple(v.card for v in tab.vars))


def served_discovery_phase(device, compiles: CompileCounter,
                           scale: float = 1.0) -> None:
    import numpy as np
    from repro.core import build_lattice, make_strategy, paper_benchmark_db
    from repro.core.variables import rind_var

    t = time.perf_counter()
    db = paper_benchmark_db("VisualGenome", seed=0, scale=scale)
    log(f"VisualGenome scale {scale}: {db.total_rows} rows, "
        f"{len(db.relations)} relationships, built in "
        f"{time.perf_counter() - t:.3f} s")
    lattice = build_lattice(db.schema, 1)
    strat = make_strategy("HYBRID", executor="sparse",
                          cache_budget_bytes=VG_CACHE_BUDGET)

    c0 = compiles.mark()
    t = time.perf_counter()
    strat.prepare(db, lattice)
    svc = strat.service()
    result = svc.discovery(max_chain_length=1).discover()
    wall = time.perf_counter() - t
    st = strat.stats
    log(f"served HYBRID discovery: wall {wall:.3f} s, positive "
        f"{st.time_positive:.3f} s, negative {st.time_negative:.3f} s, "
        f"{result.families_scored} families scored, "
        f"{compiles.since(c0)}, peak HBM {peak_bytes(device)} B")
    check(len(result.models) == len(lattice),
          f"models for {len(result.models)} of {len(lattice)} points")
    check(result.families_scored > 0, "no family was scored")

    c0 = compiles.mark()
    t = time.perf_counter()
    positives = svc.count_many([(p, None) for p in lattice])
    completes = svc.complete_many([(p, None) for p in lattice[:3]])
    log(f"served {len(positives)} count + {len(completes)} complete-CT "
        f"queries in {time.perf_counter() - t:.3f} s, "
        f"{compiles.since(c0)}, peak HBM {peak_bytes(device)} B")

    for point, tab in zip(lattice, positives):
        got = np.asarray(tab.counts)
        want = bincount_positive(db, point, tab)
        check(np.array_equal(got, want),
              f"{point}: positive table differs from NumPy bincount "
              f"(max |diff| {np.max(np.abs(got - want))})")
    log(f"positive tables of {len(positives)} points equal NumPy bincount")

    for point, pos, comp in zip(lattice, positives, completes):
        (atom,) = point.atoms
        n_pairs = (db.entities[atom.src.etype].size
                   * db.entities[atom.dst.etype].size)
        total = float(np.sum(np.asarray(comp.counts, dtype=np.float64)))
        check(abs(total - n_pairs) <= COMPLETE_RTOL * n_pairs,
              f"{point}: complete table sums to {total}, not {n_pairs}")
        r = comp.axis_of(rind_var(atom.rel))
        true_slice = np.take(np.asarray(comp.counts), 1, axis=r)
        rest = tuple(v for v in comp.vars if v.kind != "rind")
        want = np.asarray(pos.transpose_to(rest).counts)
        np.testing.assert_allclose(true_slice, want, rtol=COMPLETE_RTOL,
                                   err_msg=f"{point}: R=T slice")
    log(f"complete tables of {len(completes)} points sum to |src| x |dst| "
        f"and their R=T slices equal the positive tables")


# -- phase 3: native kernels on the path -------------------------------------

class KernelCalls:
    """Records every call of the jitted kernel wrappers in
    :mod:`repro.kernels.ops` while active: argument shapes and the
    ``interpret`` flag each call resolved to."""

    NAMES = ("_edge_segment_sum", "_ones_segment_sum")

    def __init__(self):
        self.calls = {name: [] for name in self.NAMES}

    @contextlib.contextmanager
    def recording(self):
        import jax
        from repro.kernels import ops
        originals = {name: getattr(ops, name) for name in self.NAMES}

        def recorder(name, fn):
            def call(*args, **kwargs):
                spec = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype)
                             if hasattr(a, "shape") else a for a in args)
                self.calls[name].append((spec, kwargs))
                return fn(*args, **kwargs)
            return call

        for name, fn in originals.items():
            setattr(ops, name, recorder(name, fn))
        try:
            yield self
        finally:
            for name, fn in originals.items():
                setattr(ops, name, fn)

    def check_native(self) -> None:
        """Every kernel ran, never interpreted, and lowers to Mosaic."""
        from repro.kernels import ops
        for name, calls in self.calls.items():
            check(calls, f"kernel wrapper {name} was never called")
            check(all(kw["interpret"] is False for _, kw in calls),
                  f"{name} ran in interpret mode")
            spec, kwargs = calls[-1]
            text = getattr(ops, name).lower(*spec, **kwargs).as_text()
            check("tpu_custom_call" in text,
                  f"{name} at {spec} lowers without a tpu_custom_call")
            log(f"{name}: {len(calls)} native calls; lowered at "
                f"{[getattr(a, 'shape', a) for a in spec]}: tpu_custom_call")


def family_keeps(point, schema):
    """Families of two attribute axes plus the point's indicators: small
    enough for the Pallas segment-sum route on these databases."""
    from repro.core.variables import rind_var
    attrs = point.all_ct_vars(schema, include_rind=False)
    rinds = tuple(rind_var(a.rel) for a in point.atoms)
    return [tuple(attrs[i:i + 2]) + rinds for i in range(0, len(attrs), 2)]


def strategy_tables(db, lattice, keeps, names):
    """Every (point, keep) family table of the named strategies on the
    sparse executor, fetched per point as a search round fetches them."""
    import numpy as np
    from repro.core import make_strategy
    out = {}
    for name in names:
        st = make_strategy(name, executor="sparse")
        st.prepare(db, lattice)
        for point in lattice:
            tabs = st.family_ct_many(point, keeps[point])
            for keep, tab in zip(keeps[point], tabs):
                out[name, point, keep] = np.asarray(tab.counts)
        del st, tabs
        gc.collect()        # free this strategy's cached device tables
    return out


@contextlib.contextmanager
def xla_segment_sum_route():
    """Route sparse hops to ``jax.ops.segment_sum`` for a reference run."""
    prev = os.environ.get("REPRO_SEGSUM_PALLAS")
    os.environ["REPRO_SEGSUM_PALLAS"] = "0"
    try:
        yield
    finally:
        if prev is None:
            del os.environ["REPRO_SEGSUM_PALLAS"]
        else:
            os.environ["REPRO_SEGSUM_PALLAS"] = prev


def native_kernel_phase(device, compiles: CompileCounter) -> None:
    import numpy as np
    from repro.core import CountingEngine, build_lattice, paper_benchmark_db
    from repro.core.oracle import oracle_ct
    from repro.core.strategies import STRATEGIES
    from tests.test_counting_core import tiny_db

    c0 = compiles.mark()
    t = time.perf_counter()
    kernels = KernelCalls()
    names = sorted(STRATEGIES)

    tiny = tiny_db(4)
    lattice = build_lattice(tiny.schema, 2)
    keeps = {p: [p.all_ct_vars(tiny.schema, include_rind=True)]
             + family_keeps(p, tiny.schema) for p in lattice}
    with kernels.recording():
        got = strategy_tables(tiny, lattice, keeps, names)
    for (name, point, keep), counts in got.items():
        assert_counts_equal(counts, oracle_ct(tiny, point, keep),
                            point.length,
                            f"tiny db {name} {point} vs the oracle")
    log(f"tiny db: {len(got)} family tables of {len(names)} strategies "
        f"with the Pallas kernels equal the oracle")

    # the reference: each strategy on jax.ops.segment_sum (strategies sum
    # large cells in different orders, so each is compared with itself).  Chain length 1: PRECOUNT's complete table
    # of a two-relationship Hepatitis point has 26 M cells over 13 small
    # axes, and the TPU's tiled layout of its minor axes needs 2.5 GB for it
    hep = paper_benchmark_db("Hepatitis", seed=0, scale=1.0)
    lattice = build_lattice(hep.schema, 1)
    keeps = {p: family_keeps(p, hep.schema)[:2] for p in lattice}
    with kernels.recording():
        got = strategy_tables(hep, lattice, keeps, names)
    with xla_segment_sum_route():
        want = strategy_tables(hep, lattice, keeps, names)
    for (name, point, keep), counts in got.items():
        assert_counts_equal(counts, want[name, point, keep],
                            point.length,
                            f"Hepatitis {name} {point}: Pallas vs XLA route")
    log(f"Hepatitis ({hep.total_rows} rows): {len(got)} family tables of "
        f"{len(names)} strategies equal the XLA route")

    # chains of three relationships: the middle hop carries a dense
    # message, the route of the rows segment-sum kernel
    vg = paper_benchmark_db("VisualGenome", seed=0, scale=0.001)
    chains = [p for p in build_lattice(vg.schema, 3) if p.length == 3][:4]
    with kernels.recording():
        eng = CountingEngine(vg, "sparse")
        got = [np.asarray(eng.contract(p, None).counts) for p in chains]
    with xla_segment_sum_route():
        eng = CountingEngine(vg, "sparse")
        want = [np.asarray(eng.contract(p, None).counts) for p in chains]
    for point, a, b in zip(chains, got, want):
        assert_counts_equal(a, b, 0, f"VisualGenome chain {point}")
    log(f"VisualGenome scale 0.001: positive tables of {len(chains)} "
        f"three-relationship chains equal the XLA route")

    kernels.check_native()
    log(f"native kernel phase: {time.perf_counter() - t:.3f} s, "
        f"{compiles.since(c0)}, peak HBM {peak_bytes(device)} B")


# -- --chips 4: the sharded executor ------------------------------------------

def sharded_phase(devices, compiles: CompileCounter,
                  scale: float = 1.0) -> None:
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import (CountingEngine, ShardedSparseExecutor,
                            build_lattice, paper_benchmark_db)
    from repro.core.distributed import _pad_to
    from repro.serve import CountingService

    db = paper_benchmark_db("VisualGenome", seed=0, scale=scale)
    lattice = build_lattice(db.schema, 2)
    points = ([p for p in lattice if p.length == 1]     # every relationship
              + [p for p in lattice if p.length == 2][:2])   # two chains
    mesh = Mesh(np.asarray(devices[:4]), ("data",))
    sharded = ShardedSparseExecutor(mesh=mesh, axis="data")
    check(sharded.n_ranks == 4, f"{sharded.n_ranks} ranks, not 4")

    results = {}
    for label, executor in (("one chip", "sparse"),
                            ("four chips", sharded)):
        c0 = compiles.mark()
        t = time.perf_counter()
        svc = CountingService(CountingEngine(db, executor))
        pos = [np.asarray(tab.counts)
               for tab in svc.count_many([(p, None) for p in points])]
        comp = [np.asarray(tab.counts)
                for tab in svc.complete_many([(p, None) for p in points])]
        results[label] = pos, comp
        log(f"{label}: {len(points)} positive + {len(points)} complete "
            f"tables in {time.perf_counter() - t:.3f} s, "
            f"{compiles.since(c0)}")

    (pos1, comp1), (pos4, comp4) = results["one chip"], results["four chips"]
    for point, a, b in zip(points, pos1, pos4):
        assert_counts_equal(b, a, 0, f"{point}: sharded positive table")
    for point, a, b in zip(points, comp1, comp4):
        assert_counts_equal(b, a, point.length,
                            f"{point}: sharded complete table")
    log(f"sharded == single-chip: {len(points)} positive and "
        f"{len(points)} complete tables")

    edges, _ = _pad_to(db.relations[points[0].atoms[0].rel].src, 4)
    placed = sharded.shard_rows(edges)
    layout = sorted((s.device.id, s.data.shape[0])
                    for s in placed.addressable_shards)
    check(len({d for d, _ in layout}) == 4,
          f"edge shards sit on {layout}, not four devices")
    log(f"edge shards (device id, rows): {layout}")
    log("peak HBM per device: "
        + ", ".join(f"{d.id}: {peak_bytes(d)} B" for d in devices[:4]))
    log("note: CountingRouter shard services still share one device "
        "(ROADMAP S6/R7); not exercised here")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    devices = device_phase(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileCounter()
    t = time.perf_counter()
    if args.chips == 4:
        sharded_phase(devices, compiles)
    else:
        served_discovery_phase(devices[0], compiles)
        native_kernel_phase(devices[0], compiles)
    log(f"all phases passed in {time.perf_counter() - t:.3f} s, "
        f"{compiles.since()}")
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
