"""One file per per-layer metric, found by the metric's name in
``BENCHMARK.json``: ``<name>.py`` with ``read(ctx)`` (``ctx`` is what
``benchmark/trace_view.py`` builds). A reader that finds nothing to read
returns None and the metric is left out of the line."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def reader(name):
    """The ``read`` function of ``<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"),
        os.path.join(_HERE, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
