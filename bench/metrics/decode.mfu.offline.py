"""Whole step: model FLOPs of every token decoded in the traced window
(all projections and the unembedding, attention over each token's live
context, logical shapes) over the window times the bf16 peak, in percent."""


def read(ctx):
    flops = ctx.work.get("model_flops", 0.0)
    if flops <= 0 or ctx.window_s <= 0:
        return None
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops"])
