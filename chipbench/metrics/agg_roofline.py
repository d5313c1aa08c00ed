"""agg_roofline: the aggregation's least HBM time (one read of the
(n, d) candidate stack and one write of d, at the chip's peak bytes/s)
over its device time per round, in %. Bound by memory: the rule does a
few operations per byte."""


def read(ctx):
    ms = ctx.layer_ms_per_round("aggregation")
    if not ms:
        return None
    least_ms = 1e3 * ctx.agg_bytes / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_ms / ms
