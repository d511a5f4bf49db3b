"""Per step, the device self-time of everything under the ``gmu`` module
of the gated memory units: the gate's projection, the gate on the
published scan output and the output projection; forward, recomputed
forward and backward (``benchmark/ssm_view.py``). None for a
configuration without ``mamba`` layers."""

from benchmark import ssm_view


def read(ctx):
    return ssm_view.part_ms(ctx, "gmu")
