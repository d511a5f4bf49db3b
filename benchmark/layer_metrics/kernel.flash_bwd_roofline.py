"""The backward of attention: the least time the chip could take for
the FIVE products every attention layer of the step REQUIRES
(``flops.attention_work``'s ``bwd``, summed by the builder) over the
time of every ``hvd_flash_*`` call but the forward's, however many
kernels the backward is (``hvd_flash_dkv`` + ``hvd_flash_dq`` today:
seven products a tile), found by name (``scope_view.kernel_roofline``)."""

from benchmark import scope_view


def read(ctx):
    return scope_view.kernel_roofline(ctx, ("bwd",))
