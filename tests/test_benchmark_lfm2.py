"""Tier-1 runs the LFM2-8B-A1B configuration's CPU tests (the program
against its float32 reference at tiny widths with a nonzero bias, block
by block and whole, forced and free routing; the gated short convolution
against a loop over positions; the four shares against the uncut layer;
recomputation, and what a recomputed conv block multiplies; the
defaults' case of the new ``BlockSpec`` field; ``flops_lfm2.py`` and the
parameter count by hand; the new scopes and their readers; the cell
through the CPU rehearsal). Each is collected here as a test of its own,
as ``tests/test_benchmark_trinity.py`` collects Trinity-Mini's."""

from benchmark.tests.test_lfm2 import *  # noqa: F401,F403
