"""The masked kernels' share of their roofline: the least time for what
every layer's attention REQUIRES over the pairs the selection KEEPS
(``sum_t min(t + 1, topk)`` a plane; ``flops.attention_work``: forward
two products, backward five, the panels' bytes and the bit plane's once
a direction), over ``dsa.sparse_ms`` (``benchmark/dsa_view.py``). The
count is of the mathematics, whatever tiles the kernels compute and
however many kernels they are."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.sparse_roofline(ctx)
