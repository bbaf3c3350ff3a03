"""Kernels: quant_matmul's share of its roofline over the decode steps of
the window (the executions of the decode program). Work per call from its
shapes: 4-bit codes, per-column scale and zero, bf16 activations in and
out (bench/counts.py)."""


def read(ctx):
    return ctx.roofline("quant_matmul", ctx.work.get("quant_matmul", []),
                        also=ctx.decode_kernel())
