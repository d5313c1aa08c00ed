"""agg_ms: device time of the attack and the robust aggregation (ALIE's
statistics, bucketing, the coordinate median, the Pallas kernels), per
round of the traced window."""


def read(ctx):
    return ctx.layer_ms_per_round("aggregation")
