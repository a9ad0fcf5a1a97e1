"""Attribution of device and idle time to the program's spans
(``bench/spans.py``), on two small traces recorded on a TPU v5e: the
segment-sum trace that predates program spans, and a trace of three
programs under two nested program spans and a sibling
(``spans_probe.py``); and the traced run's new per-layer metrics."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import spans, trace  # noqa: E402
from bench.tests import benchtiny, spans_probe  # noqa: E402

DATA = Path(__file__).with_name("data")
SEGSUM = DATA / "segsum_v5e.xplane.pb"
NESTED = DATA / "spans_v5e.xplane.pb"
PHASES = ("count.positive", "count.negative")
NEW_METRICS = ("positive_device_s.discovery", "negative_device_s.discovery",
               "postcount_tables_per_job.discovery",
               "h2d_bytes_per_job.discovery", "host_reads_per_job.discovery")


@pytest.fixture(scope="module")
def v5e():
    return trace.load_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def nested(v5e):
    return spans.attribute(NESTED), trace.reduce(NESTED, v5e)


def test_reduce_keeps_its_keys_and_values(v5e):
    """The device metrics the accepted benchmark reads do not move."""
    red = trace.reduce(SEGSUM, v5e, window="probe.tiny")
    assert set(red) == {"busy_s", "window_s", "device_ops", "idle_gaps",
                        "segsum"}
    assert red["busy_s"] == pytest.approx(0.003443266, rel=1e-9)
    assert red["window_s"] == pytest.approx(0.008023691, rel=1e-9)
    assert red["device_ops"] == [
        ["jit__lambda", pytest.approx(0.002633024, rel=1e-9)],
        ["jit__ones_segment_sum", pytest.approx(0.000810242, rel=1e-9)]]
    assert red["idle_gaps"] == [
        ["ReadSyncFlag", pytest.approx(0.002897863, rel=1e-9)],
        ["PjitFunction(<lambda>)", pytest.approx(0.001285071, rel=1e-9)],
        ["PjitFunction(_ones_segment_sum)",
         pytest.approx(0.000397491, rel=1e-9)]]
    assert red["segsum"]["roofline_pct"] == pytest.approx(
        0.24319660971426743, rel=1e-9)


def test_segsum_programs_link_by_run_id(v5e):
    red = trace.reduce(SEGSUM, v5e, window="probe.tiny")
    att = spans.attribute(SEGSUM, window="probe.tiny",
                          is_span=lambda name, stats: name == "probe.tiny")
    # all six programs found their launch and ran under the annotation
    assert list(att["device_by_stack"]) == [("probe.tiny",)]
    assert att["busy_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert att["device_by_span"] == [["probe.tiny", pytest.approx(
        red["busy_s"], rel=1e-9)]]
    idle = sum(att["idle_by_stack"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-6)
    # the trace predates program spans: by the marker, nothing is one
    plain = spans.attribute(SEGSUM, window="probe.tiny")
    assert plain["spans"] == 0
    assert list(plain["device_by_stack"]) == [()]
    assert spans.charged_share(plain["device_by_stack"]) == 0.0


def test_device_time_goes_to_the_innermost_span(nested):
    att, red = nested
    ops = dict(red["device_ops"])
    assert att["busy_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    by = att["device_by_stack"]
    assert by[("strategy.prepare", "count.positive")] == pytest.approx(
        ops["jit_positive_work"], rel=1e-6)
    assert by[("strategy.prepare",)] == pytest.approx(
        ops["jit_prepare_work"], rel=1e-6)
    assert by[("count.negative",)] == pytest.approx(
        ops["jit_negative_work"], rel=1e-6)
    assert spans.charged_share(by) == pytest.approx(100.0)
    pos = spans.seconds_under(by, "count.positive", PHASES)
    neg = spans.seconds_under(by, "count.negative", PHASES)
    assert pos == pytest.approx(ops["jit_positive_work"], rel=1e-6)
    assert neg == pytest.approx(ops["jit_negative_work"], rel=1e-6)
    assert pos + neg <= att["busy_s"]


def test_idle_gaps_go_to_the_span_over_their_midpoint(nested):
    att, red = nested
    idle = att["idle_by_stack"]
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    for stack, name in ((("strategy.prepare", "count.positive"),
                         "count.positive"),
                        (("strategy.prepare",), "strategy.prepare"),
                        (("count.negative",), "count.negative"),
                        ((), "outside")):
        # each host sleep is one gap, charged whole where it was slept;
        # the device's clock runs a fraction of a millisecond off the
        # host's, and opening the spans takes a little host time
        slept = spans_probe.IDLE[name]
        assert 0.9 * slept <= idle[stack] <= slept + 0.005, stack
    named = dict(att["idle_by_span"])
    assert named[spans.OUTSIDE] == pytest.approx(idle[()])
    assert 0 < spans.charged_share(idle) < 100


@pytest.fixture()
def root(tmp_path):
    yield benchtiny.make_root(tmp_path)
    benchtiny.restore_jax_config()


def test_traced_run_reads_the_phase_metrics(root, monkeypatch):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    listed = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    bench["per_layer"] += [dict(m, workloads=["t-discover"])
                           for m in listed if m["name"] in NEW_METRICS]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    real, v5e = trace.reduce, trace.load_peaks("TPU v5 lite")
    # the CPU has no device plane and no peaks: read the recorded TPU
    # trace of nested program spans instead
    monkeypatch.setattr(trace, "load_peaks", lambda kind, path=None: v5e)
    monkeypatch.setattr(trace, "reduce",
                        lambda path, peaks: real(NESTED, peaks))
    monkeypatch.setattr(spans, "locate", lambda root: NESTED)
    from bench.harness.runner import run_cell
    line = run_cell(root, "t-discover", 2 ** 33 + 9, 1.5, True,
                    require_tpu=False, log=lambda m: None)
    assert line["correct"] is True, line["checks"]
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW_METRICS) <= set(got)
    assert got["postcount_tables_per_job.discovery"] > 0
    assert got["h2d_bytes_per_job.discovery"] > 0
    assert got["host_reads_per_job.discovery"] > 0
    assert 0 < (got["positive_device_s.discovery"]
                + got["negative_device_s.discovery"]) \
        <= line["device"]["busy_s"]
    assert got["families_per_job.discovery"] > 0


def test_readers_stay_silent_without_program_spans(monkeypatch):
    """A program that records none of the new spans and opens no span
    annotations (an older one) leaves the readers nothing to read: they
    return None and do not raise."""
    from types import SimpleNamespace
    from bench.harness.spec import load_cell
    from repro.obs.trace import SpanRecord
    monkeypatch.setattr(spans, "locate", lambda root: SEGSUM)
    red = trace.reduce(SEGSUM, trace.load_peaks("TPU v5 lite"),
                       window="probe.tiny")
    old = SpanRecord(1, 1, None, "exec.positive_batch", 0.5, 0.6, None, "t")
    ctx = SimpleNamespace(kind="discover_jobs", trace=red,
                          jobs=[SimpleNamespace(t0=0.0, t1=1.0)],
                          spans=[old])
    cell = load_cell(ROOT, "vg-discover")
    for name in NEW_METRICS:
        assert cell.layer_reader(name)(ctx) is None
