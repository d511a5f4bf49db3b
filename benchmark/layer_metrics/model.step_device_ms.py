"""Median device-side duration of one run of the step program."""


def read(ctx):
    return 1e3 * ctx.step_device_s
