"""The forward of attention: the least time the chip could take for the
two products every attention layer of the step REQUIRES
(``flops.attention_work``'s ``fwd``, summed by the builder) over the
time of the ``hvd_flash_fwd`` calls (``scope_view.kernel_roofline``)."""

from benchmark import scope_view


def read(ctx):
    return scope_view.kernel_roofline(ctx, ("fwd",))
