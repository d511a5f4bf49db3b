"""Per step, the device self-time under ``hvd_loop_readout``: each
pass's output projection (itself under ``logits``) and its cross
entropy, forward, and both gradients (with the logits made again,
where the compiler does not keep them), backward
(``benchmark/loop_view.py``)."""

from benchmark import loop_view


def read(ctx):
    return loop_view.part_ms(ctx, "readout")
