"""The chain-2 cell ``vg-discover-c2`` and the counters it adds: which
metrics each discovery cell reports, the ``hops`` counter of
``count.positive`` spans and the ``blocks`` / ``blocks_built`` counters of
``count.negative`` spans, and the two readers that turn them into
``hops_per_job.discovery`` and ``mobius_blocks_built_per_job.discovery``.
The job runs on the tiny ring (``benchtiny.TINY_RING``) under the
``vg-full-c2`` configuration: 8 one-relationship and 16 two-relationship
lattice points."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.data.generate import generate  # noqa: E402
from bench.harness import system  # noqa: E402
from bench.harness.jobs import DiscoverLoop  # noqa: E402
from bench.harness.spec import load_cell  # noqa: E402
from bench.tests.benchtiny import TINY_RING  # noqa: E402
from repro.core.mobius import complete_ct_many  # noqa: E402
from repro.core.variables import rind_var  # noqa: E402
from repro.obs.trace import SpanRecord, Tracer  # noqa: E402

HOPS = "hops_per_job.discovery"
BUILT = "mobius_blocks_built_per_job.discovery"
ACCEPTED = {"families_per_job.discovery", "dispatches_per_job.discovery",
            "segsum_roofline", "device_idle_pct.discovery",
            "positive_device_s.discovery", "negative_device_s.discovery",
            "postcount_tables_per_job.discovery",
            "h2d_bytes_per_job.discovery", "host_reads_per_job.discovery",
            "positive_hit_pct.discovery"}
C2_LAYERS = {"families_per_job.discovery", "positive_device_s.discovery",
             "negative_device_s.discovery", "device_idle_pct.discovery",
             "segsum_roofline", "positive_hit_pct.discovery",
             "h2d_bytes_per_job.discovery", "host_reads_per_job.discovery",
             "postcount_tables_per_job.discovery", HOPS, BUILT}


def _names(metrics):
    return {m["name"] for m in metrics}


def _reader(metric):
    return load_cell(ROOT, "vg-discover-c2").layer_reader(metric)


def _k(keep):
    return len({v.owner[0] for v in keep if v.kind in ("rind", "edge")})


@pytest.fixture(scope="module")
def job():
    """The second job of a loop, traced from its start (the first job's
    pre-count runs before the service hands the executor its tracer)."""
    config = dict(json.loads((ROOT / "bench" / "configs" /
                              "vg-full-c2.json").read_text()),
                  schema=TINY_RING)
    tracer = Tracer(capacity=1 << 20, slow_threshold_s=None)
    loop = DiscoverLoop(config, system.build_db(generate(TINY_RING, 29)),
                        tracer)
    loop.job()
    tracer.clear()
    j = loop.job()
    return SimpleNamespace(job=j, loop=loop, spans=tracer.records(),
                           ctx=SimpleNamespace(kind="discover_jobs",
                                               trace=None, jobs=[j],
                                               spans=tracer.records()))


def test_the_chain2_cell_reports_its_metrics():
    cell = load_cell(ROOT, "vg-discover-c2")
    assert cell.config["max_chain_length"] == 2
    assert cell.workload["traffic"] == "discover_jobs"
    assert _names(cell.end_to_end) == {"setup_s", "discovery_s"}
    assert _names(cell.per_layer) == C2_LAYERS


def test_the_chain1_cell_reports_what_it_did_and_the_two_counters():
    cell = load_cell(ROOT, "vg-discover")
    assert cell.config["max_chain_length"] == 1
    assert _names(cell.end_to_end) == {"setup_s", "discovery_s"}
    assert _names(cell.per_layer) == ACCEPTED | {HOPS, BUILT}


def test_the_pre_count_walks_one_hop_per_atom(job):
    by_id = {r.span_id: r for r in job.spans}

    def in_prepare(r):
        while r.parent_id in by_id:
            r = by_id[r.parent_id]
            if r.name == "strategy.prepare":
                return True
        return False

    pos = [r for r in job.spans if r.name == "count.positive"]
    assert pos and all(in_prepare(r) for r in pos)
    assert sum(r.attrs["hops"] for r in pos) == 8 + 2 * 16
    assert sum(r.attrs["tables"] for r in pos) == len(job.loop.lattice)
    assert _reader(HOPS)(job.ctx) == 8 + 2 * 16


def test_blocks_are_two_to_the_k_of_every_table(job):
    neg = [r for r in job.spans if r.name == "count.negative"]
    assert sum(r.attrs["tables"] for r in neg) == len(job.job.calls)
    assert sum(r.attrs["blocks"] for r in neg) == sum(
        2 ** _k(keep) for _, keep, _ in job.job.calls)
    assert any(_k(keep) == 2 for _, keep, _ in job.job.calls)
    for r in neg:
        assert 0 < r.attrs["blocks_built"] <= r.attrs["blocks"]
    built = sum(r.attrs["blocks_built"] for r in neg)
    assert built < sum(r.attrs["blocks"] for r in neg)
    assert _reader(BUILT)(job.ctx) == built


def test_a_shared_sub_pattern_is_built_once(job):
    strat = system.make_strategy(job.loop.config, job.loop.executor)
    strat.prepare(job.loop.db, job.loop.lattice)
    point = next(p for p in job.loop.lattice if len(p.atoms) == 2)
    r1, r2 = (rind_var(a.rel) for a in point.atoms)
    attr = next(v for v in point.all_ct_vars(strat.db.schema)
                if v.kind == "attr")
    tracer = Tracer(slow_threshold_s=None)
    # corners {} and {r1} of the k=1 table are corners of the k=2 one
    complete_ct_many([(point, (attr, r1)), (point, (attr, r1, r2))],
                     strat.provider, tracer=tracer)
    (span,) = [r for r in tracer.records() if r.name == "count.negative"]
    assert span.attrs == {"tables": 2, "blocks": 2 + 4, "blocks_built": 4}


def test_the_readers_are_silent_without_counters():
    jobs = [SimpleNamespace(t0=0.0, t1=1.0)]
    spans = [SpanRecord(1, 1, None, "count.positive", 0.2, 0.3,
                        {"tables": 2}, "t"),
             SpanRecord(1, 2, None, "count.negative", 0.4, 0.5,
                        {"tables": 3}, "t")]
    for metric in (HOPS, BUILT):
        read = _reader(metric)
        assert read(SimpleNamespace(jobs=[], spans=[])) is None
        assert read(SimpleNamespace(jobs=jobs, spans=[])) is None
        assert read(SimpleNamespace(jobs=jobs, spans=spans)) is None
