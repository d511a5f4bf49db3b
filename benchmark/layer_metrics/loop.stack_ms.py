"""Per step, the device self-time under the ``hvd_loop_pass_<t>``
scopes of a looped stack: the blocks of every pass and the norm that
closes each, forward, recomputed forward and backward
(``benchmark/loop_view.py``). None where no instruction of the compiled
step keeps such a scope."""

from benchmark import loop_view


def read(ctx):
    return loop_view.part_ms(ctx, "stack")
