"""Seconds the program's own launch spans cover before the first device
array: ``import`` (first to last line of ``horovod_tpu/__init__.py``),
``init`` (``hvd.init()``), ``plan`` (each ``hvd.plan()``) and
``plan/apply`` (``Plan.apply()``), from ``hvd.launch_spans()``
(``benchmark/launch_view.py``). The program's share of
``launch.init_s``; the rest is the interpreter, jax's import and the
runtime's start."""

from benchmark import launch_view


def read(ctx):
    return launch_view.program_s(ctx)
