"""Per step, the device self-time of everything under the attention
modules' ``attn`` scope of a model with a per-layer attention pattern:
projections, head norms, RoPE, the flash kernels and their glue, the
gate, the output projection; forward, recomputed forward and backward
(``benchmark/swa_view.py``). None for a configuration without
``layer_types``."""

from benchmark import swa_view


def read(ctx):
    return swa_view.attn_ms(ctx)
