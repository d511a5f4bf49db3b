"""Per step, the device self-time of the optimizer's instructions that
stand alone: ``hvd_update`` and the step's top-level arithmetic
(``optax.apply_updates``), with the prefetches of the weights and
moments they read, on chip 0. A fusion carries one name: where XLA
fuses a weight's AdamW into the matmul that makes its gradient (the
large weights of GPT-2, PERF.md PR 24), that time is the backward
pass's."""

from benchmark import scope_view


def read(ctx):
    return scope_view.phase_ms(ctx, "update")
