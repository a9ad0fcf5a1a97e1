"""Tables contracted from data during search per completed discovery job:
the ``tables`` of ``count.positive`` spans with no ``strategy.prepare``
ancestor (the paper's post-count)."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    recs = [r for r in ctx.spans if lo <= r.t0 < hi]
    if not any(r.name == "strategy.prepare" for r in recs):
        return None
    by_id = {r.span_id: r for r in ctx.spans}

    def in_prepare(r):
        while r.parent_id in by_id:
            r = by_id[r.parent_id]
            if r.name == "strategy.prepare":
                return True
        return False

    n = sum(r.attrs["tables"] for r in recs
            if r.name == "count.positive" and not in_prepare(r))
    return n / len(jobs)
