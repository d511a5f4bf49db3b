"""Per step, the device self-time of everything under the ``attn``
module of the attention layers that stand BESIDE ``conv`` layers
(projections, head norms, RoPE, flash kernels and glue, output
projection; forward, recomputed forward and backward), so that the two
kinds of mixer read side by side (``benchmark/conv_view.py``). None for
a configuration without ``conv`` layers."""

from benchmark import conv_view


def read(ctx):
    return conv_view.part_ms(ctx, "attn")
