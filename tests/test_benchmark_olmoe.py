"""Tier-1 runs the OLMoE configuration's CPU tests (the program against
its float32 reference at tiny widths, forced and free routing; dropless
routing; RoPE and the q/k norm against closed forms; ``flops_moe.py`` by
hand; the expert layer's scopes and their readers). Each is collected
here as a test of its own, as ``tests/test_benchmark.py`` collects the
benchmark's arithmetic tests."""

from benchmark.tests.test_olmoe import *  # noqa: F401,F403
