"""Closed loop of discovery jobs (traffic kind ``discover_jobs``).

One job is what a user of the discovery service waits for: the
configuration's strategy is prepared on the store (its pre-count redone
into a fresh, empty cache) and ``DiscoveryService.discover()`` runs over
that strategy's ``CountingService`` with a fresh score memo.  Jobs run back
to back.  The executor, and with it every compiled program, is shared by
all jobs; each job runs on a new store version (as after a write), so no
artefact keyed by the version carries counts from one job to the next.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List

from . import system


class RecordingCounts:
    """The service's count provider, passing every call through unchanged
    and keeping what the search was handed: ``(point, keep, table)``."""

    def __init__(self, inner):
        self.inner = inner
        self.tracer = inner.tracer
        self.calls: List[tuple] = []

    @property
    def schema(self):
        return self.inner.schema

    def prepare(self, lattice):
        self.inner.prepare(lattice)

    def version(self):
        return self.inner.version()

    def family_ct(self, point, keep):
        tab = self.inner.family_ct(point, keep)
        self.calls.append((point, tuple(keep), tab))
        return tab

    def family_ct_many(self, point, keeps):
        keeps = [tuple(k) for k in keeps]
        tabs = self.inner.family_ct_many(point, keeps)
        self.calls.extend((point, k, t) for k, t in zip(keeps, tabs))
        return tabs


@dataclass
class Job:
    t0: float
    t1: float
    result: object
    calls: List[tuple]
    compiles: int
    t_prepared: float = 0.0
    cpu_s: float = 0.0

    def summary(self) -> str:
        return (f"{self.t1 - self.t0:.3f} s (prepare "
                f"{self.t_prepared - self.t0:.3f} s, process cpu "
                f"{self.cpu_s:.3f} s, {self.compiles} compiles, "
                f"{self.result.families_scored} families)")


class DiscoverLoop:
    """Drives discovery jobs on one database and one shared executor."""

    def __init__(self, config: dict, db, tracer=None):
        self.config = config
        self.db = db
        self.executor = system.make_executor(config)
        self.lattice = system.lattice(db, int(config["max_chain_length"]))
        self.tracer = tracer

    def job(self, compiles=None) -> Job:
        from repro.discover import DiscoveryService
        from repro.discover.providers import ServiceCounts
        c0 = compiles.n if compiles is not None else 0
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        self.db.version += 1
        strat = system.make_strategy(self.config, self.executor)
        strat.prepare(self.db, self.lattice)
        t_prepared = time.perf_counter()
        svc = strat.service()
        if self.tracer is not None:
            svc.set_tracer(self.tracer)
        rec = RecordingCounts(ServiceCounts(svc))
        disc = DiscoveryService(
            rec, tracer=svc.tracer,
            max_chain_length=int(self.config["max_chain_length"]),
            max_parents=int(self.config["max_parents"]),
            ess=float(self.config["ess"]))
        result = disc.discover()
        t1 = time.perf_counter()
        return Job(t0, t1, result, rec.calls,
                   (compiles.n if compiles is not None else 0) - c0,
                   t_prepared, time.process_time() - cpu0)

    def warm(self, compiles, min_jobs: int, max_jobs: int) -> List[Job]:
        """Run jobs until one compiles nothing (at least ``min_jobs``)."""
        done = []
        while len(done) < max_jobs:
            done.append(self.job(compiles))
            if len(done) >= min_jobs and done[-1].compiles == 0:
                break
        return done

    def window(self, seconds: float, compiles, on_first_job=None
               ) -> "Window":
        """Jobs back to back from now until ``seconds`` have passed; the
        job running at the close finishes but is not counted."""
        t_start = time.perf_counter()
        t_close = t_start + seconds
        jobs, c0 = [], compiles.n
        first = True
        while True:
            hook = on_first_job if first else None
            if hook is not None:
                hook("start")
            j = self.job(compiles)
            if hook is not None:
                hook("stop")
            first = False
            jobs.append(j)
            if j.t1 >= t_close:
                break
        return Window(t_start, t_close, jobs, compiles.n - c0)


@dataclass
class Window:
    t_start: float
    t_close: float
    jobs: List[Job]
    compiles: int

    @property
    def completed(self) -> List[Job]:
        return [j for j in self.jobs if j.t1 < self.t_close]
