"""The full-attention layers' flash kernels' share of their roofline:
the least time for what those layers REQUIRE (``flops.attention_work``:
forward two products and backward five over the causal pairs,
``S (S + 1) / 2``, key/value panels ``num_key_value_heads`` wide), over
``swa.full_ms`` (``benchmark/swa_view.py``)."""

from benchmark import swa_view


def read(ctx):
    return swa_view.kernels_roofline(ctx, swa_view.FULL)
