"""Per step, the device self-time under ``hvd_loop_exit``: the exit
gate, the exit distribution, its entropy and the weighted sum of the
passes' losses, forward and backward (``benchmark/loop_view.py``)."""

from benchmark import loop_view


def read(ctx):
    return loop_view.part_ms(ctx, "exit")
