"""Batched device dispatches of the executor per completed discovery job
(``exec.positive_batch`` and ``exec.mobius_batch_fused`` spans)."""

NAMES = ("exec.positive_batch", "exec.mobius_batch_fused")


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    n = sum(1 for r in ctx.spans if r.name in NAMES and lo <= r.t0 < hi)
    return n / len(jobs)
