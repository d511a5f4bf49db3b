"""A RETIRED NAME that reads ``kernel.flash_bwd_roofline``'s number: the
backward's REQUIRED five products over the time of every backward call
(``scope_view.kernel_roofline``), whatever kernels the backward is, so
that a backward without a ``hvd_flash_dkv`` still reports it. Until PR 47
it read that one kernel against the products of its own algorithm. It
stays in ``BENCHMARK.json`` only because
``tests/test_benchmark_lfm2.py`` (outside the benchmark's paths, which a
benchmark PR may not edit) holds the name in LFM2's cell: the PR that
edits that test takes the name and this file out (PERF.md section 7)."""

from benchmark import scope_view


def read(ctx):
    return scope_view.kernel_roofline(ctx, ("bwd",))
