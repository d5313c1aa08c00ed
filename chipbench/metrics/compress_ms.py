"""compress_ms: device time of compression (RandK's selection and
scaling, the wire formats), per round of the traced window. Nothing to
read where the round does not compress."""


def read(ctx):
    return ctx.layer_ms_per_round("compression")
