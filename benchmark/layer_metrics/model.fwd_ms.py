"""Per step, the device self-time of the instructions whose scope is the
forward pass (``scope_view``: a module path under ``jvp(...)``, the loss
included), on chip 0."""

from benchmark import scope_view


def read(ctx):
    return scope_view.phase_ms(ctx, "forward")
