"""Operations the forward and backward of one global step REQUIRE
(``benchmark/flops.py``: no recomputation, the causal half only) over
what the chips could do at the published bf16 peak in the time the step
program takes on the device."""


def read(ctx):
    could = ctx.step_device_s * ctx.chips * ctx.peak["bf16_flops"]
    return 100.0 * ctx.step_ops / could
