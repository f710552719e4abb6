"""The device's ``peak_bytes_in_use`` after the window, in GB (1e9):
set-up, the first rounds and the window, before the check runs."""


def read(ctx):
    return ctx.peak_bytes / 1e9
