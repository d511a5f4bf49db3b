"""Per step, the device self-time of everything under the expert
layer's ``moe`` module scope, forward and backward: router, dispatch,
the grouped matmuls, combine (``benchmark/moe_view.py``)."""

from benchmark import moe_view


def read(ctx):
    return moe_view.scope_ms(ctx, moe_view.MODULE)
