"""Of ``launch.step_trace_s``, the seconds jax spent tracing Pallas
kernel bodies: what the ``trace/kernel`` spans of
``hvd.launch_spans()`` cover inside the NEWEST ``compile/trace`` span of
the step's function (``benchmark/trace_phase_view.py``). None where the
program files no ``trace/*`` span or its log dropped one."""

from benchmark import trace_phase_view


def read(ctx):
    return trace_phase_view.part(ctx, "kernels")
