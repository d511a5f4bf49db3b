"""The flash-attention kernels together: the least time the chip could
take for the work attention REQUIRES of the step, forward two products
and backward five (``flops.attention_work``, the larger of the two
roofs a direction), over the time of every ``hvd_flash_*`` call in the
trace (``scope_view.kernel_roofline``). No call is counted and nothing
is taken from a declared call count; an earlier line gives each kernel's
time and each direction alone and says which roof binds."""

from benchmark import scope_view


def read(ctx):
    return scope_view.kernel_roofline(ctx)
