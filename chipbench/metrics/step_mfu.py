"""step_mfu: the model FLOPs the window's gradient passes require
(benchlib/flops.py) over the window times the bf16 peak of the cell's
chips, in %."""


def read(ctx):
    if not ctx.window_flops:
        return None
    return 100.0 * ctx.window_flops / (ctx.window_s * ctx.chips
                                       * ctx.peaks["bf16_flops_per_s"])
