"""Per step, the device self-time under ``hvd_moe_preroute``: the
ROUTING half of an expert layer whose router reads its block's normed
input (``BlockSpec.router_tap`` 'mixer'): the router's logits, softmax,
top-k and gates, the count of each expert's pairs and the sort that
puts the held experts' rows first, forward, recomputed forward and
backward; everything such a layer does that waits for nothing its
block's mixer makes. None for a program without the scope."""

from benchmark import moe_view

PREROUTE = "hvd_moe_preroute"   # introspect.SCOPE_MOE_PREROUTE


def read(ctx):
    return moe_view.scope_ms(ctx, PREROUTE) or None
