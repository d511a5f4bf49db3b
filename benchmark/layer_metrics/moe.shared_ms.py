"""Per step, the device self-time under ``hvd_moe_shared``: the shared
expert's three matmuls and its activation, forward, recomputed forward
and backward. None for an expert layer without a shared expert."""

from benchmark import moe_view

SHARED = "hvd_moe_shared"   # introspect.SCOPE_MOE_SHARED


def read(ctx):
    return moe_view.scope_ms(ctx, SHARED) or None
