"""Share of the traced window in which no instruction ran on chip 0."""


def read(ctx):
    return 100.0 * (1.0 - ctx.win0.busy_s / ctx.win0.window_s)
