"""Per step, the time of the three Mosaic calls ``hvd_dsa_fwd``,
``hvd_dsa_dkv`` and ``hvd_dsa_dq``: the flash kernels under a mask that
is data (``benchmark/dsa_view.py``)."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.sparse_ms(ctx)
