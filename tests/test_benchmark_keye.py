"""Tier-1 runs the Keye-VL-2.0 configuration's CPU tests (the program
against its float32 reference at tiny widths, block by block and whole,
at free and forced routing and selection, the indexer's gradients
exactly zero on both sides; the selection against a loop over rows; the
masked kernels against dense attention under the same mask; the
sectioned rotation with equal components against RoPE; the eight shares
against the uncut layer; recomputation, and that a recomputed sparse
block neither scores nor selects; the defaults' case of the new
``BlockSpec`` fields; ``flops_keye.py`` and the parameter count by hand;
the new scopes and their readers; the cell through the CPU rehearsal).
Each is collected here as a test of its own, as
``tests/test_benchmark_lfm2.py`` collects LFM2-8B-A1B's."""

from benchmark.tests.test_keye import *  # noqa: F401,F403
