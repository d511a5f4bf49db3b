"""Seconds covered by every OTHER ``compile/*`` span of the process
(``hvd.launch_spans()``, ``benchmark/launch_view.py``): what weights,
pool, optimizer state and the check's programs spend tracing, lowering
and compiling or reading the cache; the measured step's own three spans
and the baseline step's stand apart."""

from benchmark import launch_view


def read(ctx):
    return launch_view.setup_compile_s(ctx)
