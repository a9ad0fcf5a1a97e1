"""The ``positive_hit_pct.discovery`` reader: the share of the served
complete-table path's positive sub-queries answered without contracting
from data, from the ``count.complete`` spans' ``subqueries`` and
``from_data`` counters."""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import spans, trace  # noqa: E402
from bench.harness.spec import load_cell  # noqa: E402
from bench.tests import benchtiny  # noqa: E402
from repro.obs.trace import SpanRecord  # noqa: E402

METRIC = "positive_hit_pct.discovery"
NESTED = Path(__file__).with_name("data") / "spans_v5e.xplane.pb"


def _reader():
    return load_cell(ROOT, "vg-discover").layer_reader(METRIC)


def _ctx(recs, jobs=((0.0, 1.0), (1.0, 2.0))):
    return SimpleNamespace(kind="discover_jobs", trace=None,
                           jobs=[SimpleNamespace(t0=a, t1=b)
                                 for a, b in jobs],
                           spans=recs)


def _span(i, name, t0, **attrs):
    return SpanRecord(1, i, None, name, t0, t0 + 0.1, attrs or None, "t")


def test_silent_without_counters():
    read = _reader()
    assert read(_ctx([])) is None
    assert read(_ctx([_span(1, "count.positive", 0.2, tables=3),
                      _span(2, "count.complete", 0.5)])) is None
    assert read(_ctx([_span(1, "count.complete", 0.5, subqueries=4,
                            from_data=0)], jobs=())) is None


def test_reads_100_when_nothing_comes_from_data():
    recs = [_span(1, "count.complete", 0.3, subqueries=5, from_data=0),
            _span(2, "count.complete", 1.4, subqueries=7, from_data=0)]
    assert _reader()(_ctx(recs)) == pytest.approx(100.0)


def test_reads_the_share_over_the_window_jobs():
    recs = [_span(1, "count.complete", 0.3, subqueries=6, from_data=2),
            _span(2, "count.complete", 1.4, subqueries=10, from_data=1),
            _span(3, "count.positive", 1.5, tables=9),
            # before the first and after the last completed job
            _span(4, "count.complete", -0.5, subqueries=8, from_data=8),
            _span(5, "count.complete", 2.5, subqueries=8, from_data=8)]
    assert _reader()(_ctx(recs)) == pytest.approx(100.0 * (1 - 3 / 16))


@pytest.fixture()
def root(tmp_path):
    yield benchtiny.make_root(tmp_path)
    benchtiny.restore_jax_config()


def test_traced_hybrid_run_serves_from_the_precount(root, monkeypatch):
    """The tiny HYBRID cell's traced run: every positive the search's
    complete tables need is projected from the pre-count."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    listed = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    bench["per_layer"] += [
        dict(m, workloads=["t-discover"]) for m in listed
        if m["name"] in (METRIC, "postcount_tables_per_job.discovery")]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    real, v5e = trace.reduce, trace.load_peaks("TPU v5 lite")
    # the CPU has no device plane and no peaks: read a recorded TPU trace
    monkeypatch.setattr(trace, "load_peaks", lambda kind, path=None: v5e)
    monkeypatch.setattr(trace, "reduce",
                        lambda path, peaks: real(NESTED, peaks))
    monkeypatch.setattr(spans, "locate", lambda root: NESTED)
    from bench.harness.runner import run_cell
    line = run_cell(root, "t-discover", 2 ** 33 + 11, 1.5, True,
                    require_tpu=False, log=lambda m: None)
    assert line["correct"] is True, line["checks"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got[METRIC] == pytest.approx(100.0)
    assert got["postcount_tables_per_job.discovery"] == 0
