"""attack_scope_ms: device self time of the step's ops under its
``attack`` scope (the omniscient attack's statistics, ALIE's mean and std
over the (n, d) candidate stack, and the byzantine mask), per round of
the traced window (benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    return None if sp is None else sp.layer_ms_per_round("attack")
