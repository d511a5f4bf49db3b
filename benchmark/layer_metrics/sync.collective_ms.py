"""Per step, the time a collective was under way on chip 0 (synchronous
ones while they run, asynchronous ones from start to done)."""

from benchmark import trace_reduce as tr


def read(ctx):
    under_way = tr.length(tr.collective_intervals(ctx.win0))
    return 1e-6 * under_way / ctx.n_steps if under_way else None
