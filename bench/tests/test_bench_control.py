"""The comparison that decides ``correct`` fails when it should: the
program in the next lower precision than its configuration states (the
control), the timed path with an answer altered where it is produced, and
a search that stops climbing (the faults).  Tiny cells on the CPU; ``bench/control.py`` runs the same
at the cells' own sizes on the chip."""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.tests import benchtiny  # noqa: E402


@pytest.fixture()
def root(tmp_path):
    yield benchtiny.make_root(tmp_path)
    benchtiny.restore_jax_config()


def _run(root, workload, **kw):
    from bench.harness.runner import run_cell
    return run_cell(root, workload, 2 ** 32 + 3, 1.5, False,
                    require_tpu=False, log=lambda m: None, **kw)


def _failed(line):
    return [n for n, c in line["checks"].items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("workload", ["t-discover", "t-ring"])
def test_lower_precision_control_is_not_correct(root, workload):
    line = _run(root, workload, config_overrides={"dtype": "bfloat16"})
    assert line["correct"] is False
    assert _failed(line)


@pytest.mark.parametrize("workload", ["t-discover", "t-ring"])
def test_altered_answer_is_not_correct(root, workload, monkeypatch):
    from repro.core.ct import CtTable
    from repro.serve.service import CountingService
    deliver = CountingService._deliver
    seen = []

    def altered(self, e, tab):
        seen.append(1)
        if len(seen) % 3 == 0:      # every third answer, rolled one cell
            tab = CtTable(tab.vars, np.roll(np.asarray(tab.counts), 1,
                                            axis=-1))
        return deliver(self, e, tab)

    monkeypatch.setattr(CountingService, "_deliver", altered)
    line = _run(root, workload)
    assert seen
    assert line["correct"] is False
    assert _failed(line)


@pytest.mark.parametrize("workload", ["t-discover", "t-ring"])
def test_search_that_stops_climbing_is_not_correct(root, workload,
                                                   monkeypatch):
    from bench.control import no_moves
    from repro.core.search import StructureSearch
    monkeypatch.setattr(StructureSearch, "climb_point",
                        no_moves(StructureSearch.climb_point))
    line = _run(root, workload)
    assert line["correct"] is False
    assert _failed(line) == ["search_gap_rel"]
