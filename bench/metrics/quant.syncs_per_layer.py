"""Quantize walk: host syncs per layer, the `quant.sync.<site>` spans
(less `quant.sync.trace_block`, which only a traced walk makes) that start
in the traced window, over the layers the window's job quantized."""
from bench import attribution as at


def read(ctx):
    layers = ctx.run.get("layers") or 0
    n = sum(1 for e in at.spans(ctx, at.is_sync) if e[1] >= ctx.lo)
    if not n or layers <= 0:
        return None
    return n / layers
