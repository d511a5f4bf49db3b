"""The sliding-window layers' flash kernels' share of their roofline:
the least time for what those layers REQUIRE (``flops.attention_work``:
forward two products and backward five over the pairs the window keeps,
``W S - W (W - 1) / 2``, key/value panels ``num_key_value_heads`` wide),
over ``swa.window_ms`` (``benchmark/swa_view.py``)."""

from benchmark import swa_view


def read(ctx):
    return swa_view.kernels_roofline(ctx, swa_view.SLIDING)
