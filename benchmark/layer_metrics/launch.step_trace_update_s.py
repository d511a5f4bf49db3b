"""Of ``launch.step_trace_s``, the seconds tracing the gradient sync
and the optimizer's update: what the ``trace/sync`` and ``trace/update``
spans cover inside the step's newest ``compile/trace`` span
(``benchmark/trace_phase_view.py``)."""

from benchmark import trace_phase_view


def read(ctx):
    return trace_phase_view.part(ctx, "update")
