"""Per step, the part of ``loop.stack_ms`` that is a forward made AGAIN
in the backward pass (a ``rematted_computation`` segment in the
instruction's scope): what the rule that chooses
recomputation by pass costs (``benchmark/loop_view.py``)."""

from benchmark import loop_view


def read(ctx):
    return loop_view.part_ms(ctx, "recompute")
