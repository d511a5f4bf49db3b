"""Device time in the Mosaic kernels of ops/pallas_attention.py (the
calls named ``hvd_flash_*``) over the time the device was busy, on chip
0."""

from benchmark import trace_reduce as tr


def read(ctx):
    took = sum(s for s, _ in tr.kernel_seconds(ctx.win0.ops).values())
    return 100.0 * took / ctx.win0.busy_s if took else None
