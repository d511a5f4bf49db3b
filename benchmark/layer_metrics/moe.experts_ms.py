"""Per step, the device self-time under ``hvd_moe_experts``: the grouped
matmuls (gate, up, down) and the gate, forward and backward."""

from benchmark import moe_view


def read(ctx):
    return moe_view.scope_ms(ctx, moe_view.EXPERTS)
