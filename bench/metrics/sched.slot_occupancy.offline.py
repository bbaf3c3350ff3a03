"""Scheduler: the share of decode slots running, averaged over the decode
steps of the window (the program's `decode_step` span, arg `slots`)."""


def read(ctx):
    spans = ctx.spans("decode_step")
    if not spans:
        return None
    mean = sum(s["args"]["slots"] for s in spans) / len(spans)
    return 100.0 * mean / ctx.run["slots"]
