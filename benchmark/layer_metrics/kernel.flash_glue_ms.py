"""Per step, what runs under ``hvd_flash`` outside the Mosaic calls
named ``hvd_flash_*``: pads, slices, the delta row sums, layout copies
around the kernels."""

from benchmark import scope_view


def read(ctx):
    return scope_view.part_ms(ctx, "flash_glue")
