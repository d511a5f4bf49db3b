"""Per step, the device self-time under ``hvd_dsa_select``: each row's
``topk``-th largest score, the mask it makes and the mask's two bit
planes (``benchmark/dsa_view.py``)."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.part_ms(ctx, "select")
