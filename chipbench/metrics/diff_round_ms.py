"""diff_round_ms: device self time of the step's ops under ``diff_round``
(MARINA's compressed difference branch) per difference round of the
traced window. The step executions holding such ops are counted, and a
count other than the window's difference rounds by the ``Schedule``
fails the run (benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    if sp is None:
        return None
    return sp.round_kind_ms("diff_round", ctx.rounds - ctx.full_rounds)
