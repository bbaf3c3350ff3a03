"""Prefill: device idle time inside the runtime's `serve.admit` spans
(the scheduler's admission pass, batch-1 prefills, page writes and
first-token pulls), as a percent of the traced window
(bench/attribution.py)."""
from bench import attribution as at


def read(ctx):
    return at.phase_idle(ctx, at.named("serve.admit"))
