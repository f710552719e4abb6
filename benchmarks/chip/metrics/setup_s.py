"""Process start to the first timed round's dispatch: imports, traffic,
weights and surrogate fit, compiling or loading every program, the two
first rounds. The check's host copies of those rounds' states are
excluded."""


def read(ctx):
    return ctx.setup_s
