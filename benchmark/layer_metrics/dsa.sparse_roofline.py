"""The masked kernels' share of their roofline: the least time for the
attention matmuls over the pairs the selection KEEPS (``sum_t min(t + 1,
topk)`` a plane, ``flops_keye.sparse_kernel_work``), the panels' bytes
and the bit plane's, over ``dsa.sparse_ms`` (``benchmark/dsa_view.py``).
The count is of the mathematics, whatever tiles the kernels compute."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.sparse_roofline(ctx)
