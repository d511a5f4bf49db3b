"""Per step, the device self-time under ``hvd_ssm_scan`` in the Mamba
layers: the two kernels of ``ops/pallas_scan.py`` and the pads, casts
and transposes round them, forward and backward
(``benchmark/ssm_view.py``). None where no instruction of the compiled
step keeps that scope."""

from benchmark import ssm_view


def read(ctx):
    return ssm_view.part_ms(ctx, "scan")
