"""Per step, the device self-time under ``hvd_mla_latent``: both
down-projections with their norms, both up-projections, and q, k and v
put together; forward, recomputed forward and backward
(``benchmark/mla_view.py``)."""

from benchmark import mla_view


def read(ctx):
    return mla_view.scope_ms(ctx, mla_view.LATENT)
