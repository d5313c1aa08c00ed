"""log_idle_ms: device idle time inside the loop's ``log`` spans (the
log-cadence materialization of the round's metrics, which waits for the
device), per round of the traced window (benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    return None if sp is None else sp.host_ms_per_round(sp.log_idle_ns)
