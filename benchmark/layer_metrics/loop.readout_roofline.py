"""The looped model's readouts as a share of their roofline: the least
time for the operations the ``total_ut_steps`` readouts REQUIRE, forward
+ backward (``flops_ouro.readout_ops``: three matmuls of positions x
hidden x vocabulary a pass; the compute roof binds) over
``loop.readout_ms``, which also holds the cross entropy's elementwise
passes and, where the compiler does not merge it with the first, the
backward rule's second projection (``benchmark/loop_view.py``)."""

from benchmark import loop_view


def read(ctx):
    return loop_view.readout_roofline(ctx)
