"""feed_host_ms: host time in the loop's ``feed`` spans (the round's key
schedule, its minibatch and anchor programs), per round of the traced
window, on the profiler's clock (benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    return None if sp is None else sp.host_ms_per_round(sp.host_ns["feed"])
