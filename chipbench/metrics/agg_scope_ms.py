"""agg_scope_ms: device self time of the step's ops under its
``aggregate`` scope (the bucketing, the robust rule's kernels, the
flattening of the candidates into (n, d)), per round of the traced window
(benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    return None if sp is None else sp.layer_ms_per_round("aggregate")
