"""Tier-1 runs the GLM-4.7-Flash configuration's CPU tests (the program
against its float32 reference at tiny widths with a nonzero correction
bias, block by block and whole, forced and free routing; the bias's
update; recomputation; the eight shares against the uncut layer;
``flops_glm.py`` by hand; the new scopes and their readers; the cell
through the CPU rehearsal). Each is collected here as a test of its own,
as ``tests/test_benchmark_olmoe.py`` collects OLMoE's."""

from benchmark.tests.test_glm import *  # noqa: F401,F403
