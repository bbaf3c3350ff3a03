"""Quantize walk: device idle time inside the walk's `quant.gram` spans
(each tap's Gram accumulation, enqueued without a sync), as a percent of
the traced window (bench/attribution.py). It is the Gram's part of
`quant.dispatch_idle`, which counts it among the walk's dispatch."""
from bench import attribution as at


def read(ctx):
    return at.phase_idle(ctx, at.named("quant.gram"))
