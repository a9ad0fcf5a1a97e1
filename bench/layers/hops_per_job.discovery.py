"""Relationship hops contracted per completed discovery job: the ``hops``
counters of the ``count.positive`` spans (pre-count and any post-count
alike); None where no span carries the counter."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    hops = [r.attrs["hops"] for r in ctx.spans
            if r.name == "count.positive" and lo <= r.t0 < hi
            and r.attrs and "hops" in r.attrs]
    return sum(hops) / len(jobs) if hops else None
