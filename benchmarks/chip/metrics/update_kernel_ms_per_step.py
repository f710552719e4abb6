"""Device milliseconds of the packed FSGLD update kernel per chain step:
every ``fsgld_update_packed`` event of the traced window over its steps.
Nothing when the trace has no such kernel."""

KERNEL = "fsgld_update_packed"


def read(ctx):
    if ctx.trace is None:
        return None
    s = ctx.trace.op_s(KERNEL)
    if s <= 0:
        return None
    return 1e3 * s / (ctx.window.rounds * ctx.steps_per_round)
