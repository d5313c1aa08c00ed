"""grad_ms: device time of the model step (forward and backward through
``src/repro/models/``), per round of the traced window."""


def read(ctx):
    return ctx.layer_ms_per_round("model step")
