"""Blocking device-to-host reads per completed discovery job: the
``host.read`` spans."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    n = sum(1 for r in ctx.spans if r.name == "host.read" and lo <= r.t0 < hi)
    return n / len(jobs) if n else None
