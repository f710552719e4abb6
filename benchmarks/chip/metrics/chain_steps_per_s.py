"""Chain steps completed per second: every step of the window over every
second of it, first dispatch to the last round's completion."""


def read(ctx):
    return ctx.window.steps_per_s()
