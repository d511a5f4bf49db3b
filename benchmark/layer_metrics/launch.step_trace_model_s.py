"""Of ``launch.step_trace_s``, the seconds in the model's own Python:
what the ``trace/block``, ``trace/experts``, ``trace/loop_pass`` and
``trace/readout`` spans cover together inside the step's newest
``compile/trace`` span, less what the ``trace/kernel`` spans cover
(``benchmark/trace_phase_view.py``); flax's lifting and ``nn.remat``
round the blocks included."""

from benchmark import trace_phase_view


def read(ctx):
    return trace_phase_view.part(ctx, "model")
