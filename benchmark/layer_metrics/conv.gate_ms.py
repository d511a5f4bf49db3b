"""Per step, the device self-time under ``hvd_conv_gate``: the two gate
multiplies and the causal taps of the ``conv`` layers, forward,
recomputed forward and backward (``benchmark/conv_view.py``). None
where no instruction of the compiled step keeps that scope."""

from benchmark import conv_view


def read(ctx):
    return conv_view.part_ms(ctx, "gate")
