"""Per step, the device self-time of everything under the ``mamba``
module of the Mamba layers: the four projections, the taps, the step,
the selective scan and the gate; forward, recomputed forward and
backward (``benchmark/ssm_view.py``). None for a configuration without
``mamba`` layers."""

from benchmark import ssm_view


def read(ctx):
    return ssm_view.part_ms(ctx, "mixer")
