"""Möbius-join blocks projected and aligned on the host per completed
discovery job: the ``blocks_built`` counters of the ``count.negative``
spans (the blocks the joins need, less the cross-query memo's hits);
None where no span carries the counter."""


def read(ctx):
    jobs = ctx.jobs
    if not jobs:
        return None
    lo, hi = jobs[0].t0, jobs[-1].t1
    built = [r.attrs["blocks_built"] for r in ctx.spans
             if r.name == "count.negative" and lo <= r.t0 < hi
             and r.attrs and "blocks_built" in r.attrs]
    return sum(built) / len(jobs) if built else None
