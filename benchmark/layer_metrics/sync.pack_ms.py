"""Per step, what runs under ``hvd_sync`` beside the collectives: packing
the buckets, slicing the results apart, the division by the world size."""

from benchmark import scope_view


def read(ctx):
    return scope_view.part_ms(ctx, "sync_pack")
