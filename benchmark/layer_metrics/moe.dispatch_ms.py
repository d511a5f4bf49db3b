"""Per step, the device self-time of the expert layer around its
matmuls: ``hvd_moe_router`` (logits, softmax, top-k, the two auxiliary
losses), ``hvd_moe_dispatch`` (sort, gather of rows) and
``hvd_moe_combine`` (weighting and the sum per token), forward and
backward."""

from benchmark import moe_view


def read(ctx):
    return moe_view.scope_ms(ctx, moe_view.ROUTER, moe_view.DISPATCH,
                             moe_view.COMBINE)
