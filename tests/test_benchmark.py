"""Tier-1 runs the benchmark's arithmetic tests: the trace reduction, the
operation counts, the scope view, the launch view, the trace-phase view,
and that every entry
of ``BENCHMARK.json`` has its files. Each is collected here as a test of
its own. The rehearsals and the float32 reference checks of
``benchmark/tests`` take a minute and stay a run by hand
(``python -m pytest benchmark/tests -q``)."""

from benchmark.tests.test_data_driven import (  # noqa: F401
    test_every_entry_has_its_files,
    test_trace_view_on_the_recorded_trace,
)
from benchmark.tests.test_flops import *  # noqa: F401,F403
from benchmark.tests.test_launch import *  # noqa: F401,F403
from benchmark.tests.test_scope_view import *  # noqa: F401,F403
from benchmark.tests.test_trace_phase import *  # noqa: F401,F403
from benchmark.tests.test_trace_reduce import *  # noqa: F401,F403
