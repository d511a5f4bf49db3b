"""``model.bwd_ms`` in the cells that count images (a per-layer metric names the
one end-to-end metric it moves, and there that is ``images_per_s``)."""

from benchmark.layer_metrics import reader

read = reader("model.bwd_ms")
