"""Prefill: device time of the prefill programs (the batch-1 forward and
its scatter into pages) over the traced window, in percent."""

PROGRAMS = ("prefill_full", "jit_write")


def read(ctx):
    if ctx.reduced is None or ctx.window_s <= 0:
        return None
    t = sum(m[2] for m in ctx.modules if any(p in m[0] for p in PROGRAMS))
    return 100.0 * t * 1e-9 / ctx.window_s
