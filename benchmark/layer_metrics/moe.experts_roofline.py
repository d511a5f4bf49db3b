"""The expert matmuls' share of their roofline: the least time the chip
could take for their operations and bytes (forward, and two backward
matmuls for each forward one) over the self-time under
``hvd_moe_experts`` (``moe_view.experts_roofline``; an earlier line says
which roof binds)."""

from benchmark import moe_view


def read(ctx):
    return moe_view.experts_roofline(ctx)
