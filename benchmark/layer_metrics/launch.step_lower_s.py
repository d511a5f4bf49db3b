"""The measured step's own ``.lower().compile()``, second phase of
three: jaxpr to StableHLO. The NEWEST ``compile/lower`` span of
``hvd.launch_spans()`` whose ``fun_name`` is the compiled step's
(``benchmark/launch_view.py``). The three phases add up to at most
``launch.compile_s``."""

from benchmark import launch_view


def read(ctx):
    return launch_view.step_phase_s(ctx, "lower")
