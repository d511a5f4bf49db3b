"""Tier-1 runs the Ouro-2.6B configuration's CPU tests (the looped
program against its float32 reference at tiny widths: each pass's
logits, the exit distribution, the loss and every gradient leaf; a
shared weight's gradient as the sum over an untied stack of copies; the
gates shut and wide open; recomputation; causality in every pass; the
parameter count and ``flops_ouro.py`` by hand; the new scopes and their
readers on the recorded trace; the probe's defects; the cell through the
CPU rehearsal). Each is collected here as a test of its own, as
``tests/test_benchmark_phi4flash.py`` collects Phi-4-mini-flash's."""

from benchmark.tests.test_ouro import *  # noqa: F401,F403
