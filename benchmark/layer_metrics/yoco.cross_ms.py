"""Per step, the ``cross_attention`` layers' part of ``yoco.attn_ms``:
a query projection, differential attention over the keys and values
another layer published, the output projection
(``benchmark/ssm_view.py``). None for a configuration without
``mamba`` layers."""

from benchmark import ssm_view


def read(ctx):
    return ssm_view.part_ms(ctx, "cross")
