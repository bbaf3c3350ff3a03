"""Whole quantize job: the algorithm's FLOPs for the layers quantized in
the traced window (tap Grams, layer forward, COMQ sweeps; bench/drivers/
quantize.py `work`) over the window times the bf16 peak, in percent."""


def read(ctx):
    flops = ctx.work.get("algorithm_flops", 0.0)
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
