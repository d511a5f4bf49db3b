"""Pallas kernel bodies jax traced for the measured step: the number
of ``trace/kernel`` spans inside the step's newest ``compile/trace``
span (``benchmark/trace_phase_view.py``). A jitted callee files one on a
tracing-cache miss only; beside the log's ``tpu_custom_calls`` it says
how many bodies a Mosaic call cost. Repeats exactly."""

from benchmark import trace_phase_view


def read(ctx):
    return trace_phase_view.part(ctx, "bodies")
