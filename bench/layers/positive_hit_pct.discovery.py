"""Share of the served complete-table path's positive sub-queries answered
without contracting from data, in %: 100 x (1 - from_data / subqueries),
summed over the ``count.complete`` spans of the completed discovery jobs."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    recs = [r for r in ctx.spans
            if r.name == "count.complete" and lo <= r.t0 < hi
            and r.attrs and "subqueries" in r.attrs
            and "from_data" in r.attrs]
    asked = sum(r.attrs["subqueries"] for r in recs)
    if not asked:
        return None
    return 100.0 * (1.0 - sum(r.attrs["from_data"] for r in recs) / asked)
