"""Tier-1 runs the SmallThinker-21BA3B configuration's CPU tests (the
program against its float32 reference at tiny widths, whole, at free
and forced routing; the router's tap against the other tap's gradients;
ReGLU in the dense and the grouped feed-forward against a plain
expression; the gates against ``route``; the four shares against the
uncut layer; recomputation; causality; ``router_tap`` 'ffn' against the
parent's recorded jaxprs; ``flops_smallthinker.py`` and the parameter
count by hand; the new scope and its reader; the cell through the CPU
rehearsal). Each is collected here as a test of its own, as
``tests/test_benchmark_trinity.py`` collects Trinity-Mini's."""

from benchmark.tests.test_smallthinker import *  # noqa: F401,F403
