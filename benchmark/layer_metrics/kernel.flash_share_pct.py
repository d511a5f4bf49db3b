"""Device time in the three Mosaic kernels of ops/pallas_attention.py
over the time the device was busy, on chip 0."""

from benchmark import trace_reduce as tr


def read(ctx):
    kernel = [e for e in ctx.win0.ops if tr.flash_kernel(e.name)]
    if not kernel:
        return None
    return 100.0 * sum(e.seconds for e in kernel) / ctx.win0.busy_s
