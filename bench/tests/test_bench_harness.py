"""The harness end to end on the CPU, at tiny sizes: a cell defined only
by new files (a configuration, a traffic mix and a layer reader in a
directory of their own) is found by name and runs, and its line is sound.
The chip check is skipped here; ``bench/run.py`` itself refuses a host
without a TPU."""

import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.tests import benchtiny  # noqa: E402


@pytest.fixture()
def root(tmp_path):
    yield benchtiny.make_root(tmp_path)
    benchtiny.restore_jax_config()


def _run(root, workload, trace=False, seed=2 ** 33 + 7, **kw):
    from bench.harness.runner import run_cell
    return run_cell(root, workload, seed, 1.5, trace,
                    require_tpu=False, log=lambda m: None, **kw)


def test_new_files_define_a_cell_that_runs(root):
    line = _run(root, "t-discover")
    assert line["correct"] is True, line["checks"]
    assert set(line["metrics"]) == {"setup_s", "discovery_s"}
    assert line["metrics"]["discovery_s"]["value"] > 0
    assert list(line)[-2:] == ["checks", "_check_lines"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_traced_run_reads_the_new_layer(root, monkeypatch):
    from bench import trace
    recorded = Path(__file__).with_name("data") / "segsum_v5e.xplane.pb"
    real, v5e = trace.reduce, trace.load_peaks("TPU v5 lite")
    # the CPU has no device plane and no peaks: reduce the recorded TPU
    # trace instead
    monkeypatch.setattr(trace, "load_peaks", lambda kind, path=None: v5e)
    monkeypatch.setattr(trace, "reduce", lambda path, peaks: real(
        recorded, peaks, window="probe.tiny"))
    line = _run(root, "t-discover", trace=True)
    assert line["correct"] is True
    assert line["metrics"]["jobs_seen"]["value"] >= 1
    assert line["metrics"]["families_per_job.discovery"]["value"] > 0
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]


def test_relabelled_seeds_ask_for_the_same_work(root):
    a = _run(root, "t-ring")
    b = _run(root, "t-ring", seed=2 ** 31 + 5)
    assert a["correct"] is True and b["correct"] is True, (a, b)
    assert a["checks"] == b["checks"]


def test_command_refuses_a_host_without_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "vg-discover", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
