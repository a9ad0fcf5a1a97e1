"""Share of the relationship hops contracted in the completed discovery
jobs that found their edge columns already on the device, in %: 100 x
sum ``edges_resident`` / sum (``edges_resident`` + ``edges_uploaded``)
over the ``count.positive`` spans; None where no span carries the
counters."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    recs = [r for r in ctx.spans
            if r.name == "count.positive" and lo <= r.t0 < hi
            and r.attrs and "edges_resident" in r.attrs
            and "edges_uploaded" in r.attrs]
    resident = sum(r.attrs["edges_resident"] for r in recs)
    hops = resident + sum(r.attrs["edges_uploaded"] for r in recs)
    return 100.0 * resident / hops if hops else None
