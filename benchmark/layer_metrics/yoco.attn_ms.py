"""Per step, the device self-time of everything under the ``attn``
module of the attention layers that stand beside Mamba layers, window,
full and cross alike (projections, the flash kernels and their glue, the
differential subtraction and norm, the output projection; forward,
recomputed forward and backward; ``benchmark/ssm_view.py``). None for a
configuration without ``mamba`` layers."""

from benchmark import ssm_view


def read(ctx):
    return ssm_view.part_ms(ctx, "attn")
