"""Per step, the device self-time of the backward pass (``transpose(`` in
the scope; a rematerialised forward counts here, and so does the part
of the optimizer that XLA fuses into a weight-gradient matmul), on chip
0."""

from benchmark import scope_view


def read(ctx):
    return scope_view.phase_ms(ctx, "backward")
