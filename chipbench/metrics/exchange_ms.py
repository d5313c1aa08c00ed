"""exchange_ms: device self time of the step program's collectives
(``spans.COLLECTIVES``: the all-to-all of the sharded aggregation, its
all-gather, the all-reduces of the attack's statistics), per round of the
traced window and chip (benchlib/spans.py). Nothing to read where the
step exchanges nothing between chips."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    if sp is None or not sp.device.exchange_ns:
        return None
    return sp.device.exchange_ns / 1e6 / ctx.rounds
