"""Phase spans of a discovery job: the pre-count, the positive and negative
counting phases, and the host-device boundary, as a tiny served HYBRID
discovery on the sparse executor records them.

* ``strategy.prepare`` once per job, enclosing the pre-count's
  ``count.positive`` spans;
* ``count.positive`` ``tables`` add up to the tables contracted from data;
* ``host.stage`` ``nbytes`` add up to the host arrays uploaded;
* ``NULL_TRACER`` records and opens nothing and changes no table;
* a live span is a profiler annotation exactly while annotations are on.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import build_lattice, make_strategy
from repro.core.executors import SparseExecutor
from repro.discover import DiscoveryService
from repro.obs import NULL_TRACER, Tracer, profile
from repro.obs.trace import PROGRAM_SPAN_STAT
from tests.test_counting_core import tiny_db

CHAIN = 2


def _job(tracer, executor=None):
    """One discovery job as a service runs it: the strategy's pre-count on
    a long-lived executor, then discovery over the strategy's counting
    service."""
    db = tiny_db(0)
    ex = executor if executor is not None else SparseExecutor()
    ex.tracer = tracer
    strat = make_strategy("HYBRID", executor=ex)
    strat.prepare(db, build_lattice(db.schema, CHAIN))
    svc = strat.service()
    svc.set_tracer(tracer)
    result = DiscoveryService(svc, max_chain_length=CHAIN).discover()
    return strat, result


def _ancestors(rec, by_id):
    while rec.parent_id in by_id:
        rec = by_id[rec.parent_id]
        yield rec


def test_one_prepare_span_encloses_the_precount():
    tracer = Tracer()
    strat, _ = _job(tracer)
    recs = tracer.records()
    by_id = {r.span_id: r for r in recs}
    (prep,) = [r for r in recs if r.name == "strategy.prepare"]
    assert prep.attrs == {"strategy": "HYBRID"}
    pre = [r for r in recs if r.name == "count.positive"
           and prep in _ancestors(r, by_id)]
    # HYBRID pre-counts one full positive table per lattice point
    assert sum(r.attrs["tables"] for r in pre) == len(strat.lattice)
    assert all(prep.t0 <= r.t0 <= r.t1 <= prep.t1 for r in pre)
    later = [r for r in recs if r.t0 > prep.t1]
    assert later and not any(r.name == "strategy.prepare" for r in later)


def test_positive_tables_are_the_contractions_from_data():
    tracer = Tracer()
    ex = SparseExecutor()
    contracted = []
    positive, stacked = ex.positive, ex._positive_stacked

    def counted_positive(db, plan, stats=None):
        contracted.append(1)
        return positive(db, plan, stats)

    def counted_stacked(db, plans, stats):
        out = stacked(db, plans, stats)
        contracted.append(len(plans))
        return out

    ex.positive, ex._positive_stacked = counted_positive, counted_stacked
    _job(tracer, ex)
    spans = [r for r in tracer.records() if r.name == "count.positive"]
    assert spans
    assert sum(r.attrs["tables"] for r in spans) == sum(contracted)


def test_stage_bytes_are_the_host_arrays_uploaded(monkeypatch):
    uploaded = []
    real = jnp.asarray

    def recording(x, *a, **kw):
        if isinstance(x, np.ndarray):
            uploaded.append(x.nbytes)
        return real(x, *a, **kw)

    tracer = Tracer()
    monkeypatch.setattr(jnp, "asarray", recording)
    _job(tracer)
    monkeypatch.undo()
    staged = [r.attrs["nbytes"] for r in tracer.records()
              if r.name == "host.stage"]
    assert staged and sum(staged) == sum(uploaded)
    reads = [r for r in tracer.records() if r.name == "host.read"]
    assert reads and all(r.attrs["nbytes"] > 0 and r.attrs["site"]
                         for r in reads)


def test_null_tracer_records_and_opens_nothing(monkeypatch):
    opened = []
    monkeypatch.setattr(profile, "_trace_annotation",
                        lambda name, **stats: opened.append(name)
                        or profile._NULL)
    profile.enable()
    try:
        off, res_off = _job(NULL_TRACER)
    finally:
        profile.disable()
    assert opened == []
    assert NULL_TRACER.records() == []
    tracer = Tracer()
    on, res_on = _job(tracer)
    assert tracer.records()
    assert res_on.signature() == res_off.signature()
    assert res_on.score == res_off.score
    for point in off.lattice:
        key = ("full", "sparse", point.atoms)
        a, b = off.engine.cache.get(key), on.engine.cache.get(key)
        assert a.vars == b.vars
        np.testing.assert_array_equal(np.asarray(a.counts),
                                      np.asarray(b.counts))


def test_span_is_an_annotation_only_while_enabled(monkeypatch):
    calls = []

    class Recorder:
        def __init__(self, name, **stats):
            self.name = name
            calls.append(("new", name, stats))

        def __enter__(self):
            calls.append(("enter", self.name))
            return self

        def __exit__(self, *exc):
            calls.append(("exit", self.name))
            return False

    monkeypatch.setattr(profile, "_trace_annotation", Recorder)
    tracer = Tracer()
    with tracer.span("before"):
        pass
    assert calls == []
    profile.enable()
    try:
        with tracer.span("outer") as outer:
            with tracer.span("inner", tables=2) as inner:
                pass
    finally:
        profile.disable()
    assert calls == [
        ("new", "outer", {PROGRAM_SPAN_STAT: outer.span_id}),
        ("enter", "outer"),
        ("new", "inner", {PROGRAM_SPAN_STAT: inner.span_id}),
        ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]
    with tracer.span("after"):
        pass
    assert len(calls) == 6
    assert [r.name for r in tracer.records()] == ["before", "inner",
                                                  "outer", "after"]


@pytest.fixture(autouse=True)
def _profile_off():
    yield
    profile.disable()
