"""``launch.step_trace_s`` less everything any ``trace/*`` span covers
inside it: jax's differentiation and transposition, the builder's loss,
``shard_map`` (``benchmark/trace_phase_view.py``). With
``launch.step_trace_kernels_s``, ``_model_s`` and ``_update_s`` it adds
up to ``launch.step_trace_s``."""

from benchmark import trace_phase_view


def read(ctx):
    return trace_phase_view.part(ctx, "rest")
