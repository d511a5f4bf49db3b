"""The measured step's own ``.lower().compile()``, third phase of
three: XLA's compile on a cold run, the persistent cache's read and the
executable's load on a warm one. The NEWEST ``compile/backend`` span of
``hvd.launch_spans()`` whose ``fun_name`` is the compiled step's
(``benchmark/launch_view.py``). The three phases add up to at most
``launch.compile_s``."""

from benchmark import launch_view


def read(ctx):
    return launch_view.step_phase_s(ctx, "backend")
