"""Per step, the device self-time of everything under the attention
modules' ``attn`` scope of a model whose queries choose their keys:
projections, head norms, RoPE, the indexer, the selection, the masked
kernels and their glue, the output projection; forward, recomputed
forward and backward (``benchmark/dsa_view.py``). None for a step
without the masked kernels."""

from benchmark import dsa_view


def read(ctx):
    return dsa_view.part_ms(ctx, "attn")
