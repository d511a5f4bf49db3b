"""Per step, the device self-time of everything under the ``conv``
module of the gated short-convolution layers: the in- and
out-projections, the two gates and the taps; forward, recomputed
forward and backward (``benchmark/conv_view.py``). None for a
configuration without ``conv`` layers."""

from benchmark import conv_view


def read(ctx):
    return conv_view.part_ms(ctx, "mixer")
