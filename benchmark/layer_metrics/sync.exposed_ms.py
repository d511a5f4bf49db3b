"""Per step, the part of the collectives' time on chip 0 during which
no other instruction ran there."""

from benchmark import trace_reduce as tr


def read(ctx):
    if not tr.collective_intervals(ctx.win0):
        return None
    return 1e-6 * tr.exposed_collective(ctx.win0) / ctx.n_steps
