"""The ``conv`` layers' gates and taps as a share of their roofline: the
least time for the bytes those elementwise passes must move (the
in-projection's product in, the gated convolution out; backward the
product and the output's gradient in, the product's gradient out) and
their operations (``flops_lfm2.conv_gate_work``; the memory roof binds)
over ``conv.gate_ms`` (``benchmark/conv_view.py``)."""

from benchmark import conv_view


def read(ctx):
    return conv_view.gate_roofline(ctx)
