"""Per step, the time of the three flash kernels in the SLIDING-window
layers (told by the ``layer_<i>`` of the call's scope and the
configuration's ``layer_types``; ``benchmark/swa_view.py``)."""

from benchmark import swa_view


def read(ctx):
    return swa_view.kernels_ms(ctx, swa_view.SLIDING)
