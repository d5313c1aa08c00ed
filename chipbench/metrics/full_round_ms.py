"""full_round_ms: device self time of the step's ops under ``full_round``
(MARINA's full-gradient branch, c_k = 1) per full-gradient round of the
traced window. The step executions holding such ops are counted, and a
count other than the window's full-gradient rounds by the ``Schedule``
fails the run (benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    if sp is None:
        return None
    return sp.round_kind_ms("full_round", ctx.full_rounds)
