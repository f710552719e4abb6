"""90th percentile of every round's wall time in the window, dispatch to
completion (``block_until_ready``), in milliseconds."""
from window import percentile


def read(ctx):
    return percentile(ctx.window.round_s(), 90) * 1e3
