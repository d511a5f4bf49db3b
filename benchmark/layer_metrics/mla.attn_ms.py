"""Per step, the device self-time of everything under the
latent-attention module's ``attn`` scope: the latent projections and
their norms, RoPE, the flash kernels and their glue, the output
projection; forward, recomputed forward and backward
(``benchmark/mla_view.py``). None for a model with plain heads."""

from benchmark import mla_view


def read(ctx):
    return mla_view.scope_ms(ctx, mla_view.MODULE)
