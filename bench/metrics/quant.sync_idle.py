"""Quantize walk: device idle time inside the walk's host syncs (its
`quant.sync.<site>` spans, less `quant.sync.trace_block`, which only a
traced walk makes), as a percent of the traced window
(bench/attribution.py)."""
from bench import attribution as at


def read(ctx):
    return at.phase_idle(ctx, at.is_sync)
