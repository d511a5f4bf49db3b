"""Per step, the device self-time under ``hvd_dsa_index``: the indexer's
three projections, its norm, RoPE and the (S, S) float32 scores
(``benchmark/dsa_view.py``)."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.part_ms(ctx, "index")
