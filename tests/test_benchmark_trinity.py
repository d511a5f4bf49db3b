"""Tier-1 runs the Trinity-Mini configuration's CPU tests (the program
against its float32 reference at tiny widths with a nonzero correction
bias, block by block and whole, forced and free routing; sliding and
full layers through the dense path and the flash kernels; the eight
shares against the uncut layer; recomputation; the defaults' case of
every new ``BlockSpec`` field; ``flops_afmoe.py`` by hand; the new
scopes and their readers; the cell through the CPU rehearsal). Each is
collected here as a test of its own, as ``tests/test_benchmark_glm.py``
collects GLM's."""

from benchmark.tests.test_trinity import *  # noqa: F401,F403
