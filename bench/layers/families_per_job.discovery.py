"""Family tables scored per completed discovery job."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    return sum(j.result.families_scored for j in jobs) / len(jobs)
