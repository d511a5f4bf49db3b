"""The planner's predicted ``step_comm_ms`` over the measured
``sync.collective_ms``: 1.0 is a cost model that tells the truth."""

from benchmark import trace_reduce as tr


def read(ctx):
    under_way = tr.length(tr.collective_intervals(ctx.win0))
    if not under_way:
        return None
    measured_ms = 1e-6 * under_way / ctx.n_steps
    return ctx.plan.to_json()["step_comm_ms"] / measured_ms
