"""Scheduler: device idle time inside the runtime's other per-step host
phases, `serve.emit` (the per-slot emit/retire loop), `serve.pages`
(page growth, block tables and their upload) and `serve.dispatch` (the
decode program's enqueue), as a percent of the traced window
(bench/attribution.py)."""
from bench import attribution as at


def read(ctx):
    return at.phase_idle(ctx, at.named("serve.emit", "serve.pages",
                                       "serve.dispatch"))
