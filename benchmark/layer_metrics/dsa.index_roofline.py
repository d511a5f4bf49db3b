"""The indexer's and the selection's share of their roofline: the least
time for ``index_heads`` dot products over every causal pair a layer,
the bytes of ``qI``, ``kI`` and ``w`` and one pass over the float32
scores (``flops_keye.index_work``), over ``dsa.index_ms`` +
``dsa.select_ms`` (``benchmark/dsa_view.py``)."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.index_roofline(ctx)
