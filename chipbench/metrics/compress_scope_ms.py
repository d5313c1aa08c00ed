"""compress_scope_ms: device self time of the step's ops under its
``compress`` scope (RandK's selection and scaling, the wire packing, the
dense path's g^k + Q(delta)), per round of the traced window
(benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    return None if sp is None else sp.layer_ms_per_round("compress")
