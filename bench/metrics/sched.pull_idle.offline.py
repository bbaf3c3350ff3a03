"""Scheduler: device idle time inside the runtime's `serve.pull` spans
(picking the step's tokens and pulling them to the host), as a percent
of the traced window (bench/attribution.py)."""
from bench import attribution as at


def read(ctx):
    return at.phase_idle(ctx, at.named("serve.pull"))
