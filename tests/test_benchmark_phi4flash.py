"""Tier-1 runs the Phi-4-mini-flash-reasoning configuration's CPU tests
(the program against its float32 reference at tiny widths, block by
block and whole, loss and every gradient leaf; the selective scan's
kernels, interpreted, and its plain path against a loop over positions,
forward and all six gradients, at a length that is no whole number of
chunks; the published arrays' gradients with two readers each;
recomputation, and that a recomputed reader runs no scan and no key or
value projection; the kept layers' ``lambda_init``, the parameter count
and ``flops_phi4flash.py`` by hand; the defaults' case of each new
``BlockSpec`` field; the new scopes and their readers on the recorded
trace; the cell through the CPU rehearsal). Each is collected here as a
test of its own, as ``tests/test_benchmark_keye.py`` collects
Keye-VL-2.0's."""

from benchmark.tests.test_phi4flash import *  # noqa: F401,F403
