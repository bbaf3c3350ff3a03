"""Quantize walk: device idle time inside the walk's `layer` spans and
outside every host sync (`quant.sync.<site>`, `trace_block` included):
the host dispatching the walk's small programs, as a percent of the
traced window (bench/attribution.py)."""
from bench import attribution as at
from bench import trace as tr


def read(ctx):
    layers = at.spans(ctx, at.named("layer"))
    if ctx.reduced is None or ctx.hi <= ctx.lo or not layers:
        return None
    syncs = tr.union(at.spans(ctx, lambda n: n.startswith(at.SYNC)))
    return at.idle_share(ctx, at.subtract(tr.union(layers), syncs))
