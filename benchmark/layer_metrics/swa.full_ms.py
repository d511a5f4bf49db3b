"""Per step, the time of the three flash kernels in the FULL-attention
layers (``benchmark/swa_view.py``)."""

from benchmark import swa_view


def read(ctx):
    return swa_view.kernels_ms(ctx, swa_view.FULL)
