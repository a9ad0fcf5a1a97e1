"""Bytes staged host to device per completed discovery job: the
``nbytes`` of the ``host.stage`` spans."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    staged = [r.attrs["nbytes"] for r in ctx.spans
              if r.name == "host.stage" and lo <= r.t0 < hi]
    return sum(staged) / len(jobs) if staged else None
