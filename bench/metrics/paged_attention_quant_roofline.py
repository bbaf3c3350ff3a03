"""Kernels: paged_attention_quant's share of its roofline over the decode
steps of the window (int8 or 4-bit pages). Work per step from each slot's
live context: K and V codes at logical width, q in, output out
(bench/counts.py)."""


def read(ctx):
    if not ctx.run.get("kv_bits"):
        return None
    return ctx.roofline("paged_attention_quant",
                        ctx.work.get("paged_attention", []))
