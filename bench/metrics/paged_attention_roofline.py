"""Kernels: paged_attention's share of its roofline over the decode steps
of the window. Work per step from each slot's live context at logical
width: K and V read, q in, output out (bench/counts.py)."""


def read(ctx):
    if ctx.run.get("kv_bits"):
        return None
    return ctx.roofline("paged_attention",
                        ctx.work.get("paged_attention", []))
