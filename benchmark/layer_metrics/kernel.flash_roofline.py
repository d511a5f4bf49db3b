"""The three flash-attention kernels together: the least time the chip
could take for the operations and bytes their algorithm needs
(``flops.flash_kernel_work``, the larger of the two roofs per call)
over the time they took in the trace. An earlier line gives each kernel
alone and says which roof binds."""

from benchmark import flops
from benchmark import trace_reduce as tr


def read(ctx):
    took = sum(e.seconds for e in ctx.win0.ops if tr.flash_kernel(e.name))
    if not took or not ctx.kernels:
        return None
    least = sum(calls * flops.roofline_seconds(ops, nbytes, ctx.peak)[0]
                for calls, ops, nbytes in ctx.kernels.values())
    return 100.0 * least * ctx.n_steps / took
