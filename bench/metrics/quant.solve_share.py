"""Solver: the share of the traced window covered by the pipeline's
`leaf_solve` spans (their union; with a tracer each span blocks on its
solved codes), in percent."""


def read(ctx):
    spans = ctx.spans("leaf_solve")
    win = ctx.run.get("window", {})
    length = (win.get("t1", 0.0) - win.get("t0", 0.0)) * 1e6
    if not spans or length <= 0:
        return None
    ivs = sorted((s["ts"], s["ts"] + s["dur"]) for s in spans)
    covered, end = 0.0, float("-inf")
    for a, b in ivs:
        if b > end:
            covered += b - max(a, end)
            end = b
    return 100.0 * covered / length
