"""Device: the share of the traced window in which no operation ran on the
chip, in percent (1 - busy / window, busy the union of operation time)."""


def read(ctx):
    if ctx.reduced is None or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s() / ctx.window_s)
