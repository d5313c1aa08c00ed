"""unscoped_share: the step program's device self time under none of the
five layer scopes (the key splits, MARINA's coin) over the step
program's device time, in % (benchlib/spans.py)."""
from benchlib import spans


def read(ctx):
    sp = spans.read(ctx)
    if sp is None or not sp.has_layers or not sp.device.step_ns:
        return None
    return (100.0 * sp.device.layer_ns.get(spans.UNSCOPED, 0.0)
            / sp.device.step_ns)
