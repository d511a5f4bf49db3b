"""Per step, the vocabulary-sized ends of the model, forward and
backward: ``embed``, ``logits`` and the loss, with the compiler's loops
that re-tile their arrays, on chip 0."""

from benchmark import scope_view


def read(ctx):
    return scope_view.part_ms(ctx, "head")
