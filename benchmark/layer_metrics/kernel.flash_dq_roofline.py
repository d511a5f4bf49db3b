"""The ``hvd_flash_dq`` kernel alone, found by its name: the least time
the chip could take for its operations and bytes over the time its calls
took (``scope_view.kernel_roofline``)."""

from benchmark import scope_view


def read(ctx):
    return scope_view.kernel_roofline(ctx, "dq")
