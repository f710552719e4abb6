"""Host milliseconds a round's dispatch takes to return (the program's
``engine.run`` call, before the wait), mean over the traced window."""


def read(ctx):
    if ctx.trace is None:
        return None
    d = ctx.window.dispatch_s()
    return 1e3 * sum(d) / len(d)
