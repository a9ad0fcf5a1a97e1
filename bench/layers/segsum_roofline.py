"""Segment-sums (XLA scatter-add and the Pallas segment-sum kernels) as a
share of their roofline, in %: the least time the chip's peak bandwidth
and compute allow for the work the algorithm needs, over their device
time in the traced window."""


def read(ctx):
    seg = (ctx.trace or {}).get("segsum")
    return seg["roofline_pct"] if seg else None
