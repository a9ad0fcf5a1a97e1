"""The trace reduction, on a small trace recorded on a TPU v5e: a Pallas
segment-sum and an XLA segment-sum, three times each, inside a host
annotation."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import trace  # noqa: E402

DATA = Path(__file__).with_name("data") / "segsum_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(DATA, trace.load_peaks("TPU v5 lite"),
                        window="probe.tiny")


def test_busy_within_window(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    # six programs of about 0.4 ms and 0.9 ms each
    assert 0.003 < reduced["busy_s"] < 0.006


def test_programs_and_gaps(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert "jit__ones_segment_sum" in names and "jit__lambda" in names
    assert sum(t for _, t in reduced["device_ops"]) == pytest.approx(
        reduced["busy_s"], rel=1e-6)
    idle = sum(t for _, t in reduced["idle_gaps"])
    assert idle == pytest.approx(reduced["window_s"] - reduced["busy_s"],
                                 rel=1e-6)


def test_segsum_roofline_counts_the_algorithm(reduced):
    seg = reduced["segsum"]
    # calls of out[seg[e]] += w[e] over 100352 padded edges into 1024
    # padded segments, those wholly inside the window: ids and weights
    # read, sums written
    calls = seg["ops"] / 100352
    assert calls in (1, 2, 3)
    assert seg["bytes"] == calls * (100352 * 4 + 100352 * 4 + 1024 * 4)
    assert 0 < seg["roofline_pct"] <= 100


def test_segsum_work_parses_scatter():
    hlo = ("%fusion = f32[2400000]{0:T(1024)} fusion(f32[2400000]{0:T(1024)}"
           " %copy.2, s32[1900000]{0:T(1024)S(1)} %reduce, "
           "f32[1900000]{0:T(1024)S(1)} %custom-call), kind=kCustom")
    ops, nbytes = trace.segsum_work(hlo)
    assert ops == 1900000
    assert nbytes == 1900000 * 4 + 1900000 * 4 + 2400000 * 4
    assert trace.segsum_work("%copy = f32[8]{0} copy(f32[8]{0} %x)") is None


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        trace.load_peaks("TPU v9 imaginary")
