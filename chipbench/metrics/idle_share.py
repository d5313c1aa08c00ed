"""idle_share: the share of the traced window in which no operation ran
on the device, in % (1 - union of op intervals / window). A union longer
than the window is a miscount of the trace's ops, and fails the run."""


def read(ctx):
    if ctx.busy_s > ctx.window_s:
        from benchlib.harness import BenchError
        raise BenchError(f"device busy {ctx.busy_s} s in a traced window "
                         f"of {ctx.window_s} s")
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
