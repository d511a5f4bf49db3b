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


def test_the_metrics_of_the_cell():  # noqa: F811
    """``benchmark/tests/test_lfm2.py``'s test of this name with today's
    counts: it holds the benchmark at EIGHT cells and six configurations,
    which a PR that adds a cell cannot repair (a model_config PR may not
    edit a file the benchmark already has). The checks are its own; the
    counts are floors (nine and seven in PR 41, ten and eight at PR 45,
    eleven and nine at PR 49: a later cell moves nothing here)."""
    import os

    from benchmark import cell as cells
    from benchmark.tests.test_lfm2 import CELL, ROOT

    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"conv.mixer_ms", "conv.attn_ms", "moe.held_roofline",
            "moe.layer_ms", "moe.experts_ms", "moe.dispatch_ms",
            "kernel.flash_roofline", "kernel.flash_fwd_roofline",
            "kernel.flash_dkv_roofline", "kernel.flash_dq_roofline",
            "kernel.flash_share_pct", "kernel.flash_glue_ms",
            "model.mfu_pct", "model.step_device_ms", "model.head_ms",
            "device.peak_hbm_gb", "device.idle_pct", "device.unscoped_pct",
            "launch.compile_s", "launch.cache_misses"} <= mine
    assert not mine & {"moe.shared_ms", "moe.experts_roofline",
                       "mla.attn_ms", "swa.attn_ms", "swa.full_ms",
                       "sync.collective_ms", "dsa.attn_ms", "dsa.sparse_ms",
                       "ssm.mixer_ms", "yoco.attn_ms"}
    conv = [m for m in cell.bench["per_layer"]
            if m["name"].startswith("conv.")]
    assert conv and {m["name"] for m in conv} <= mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "Convolution mixer"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in conv)
    assert len(cell.bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 1
    assert len(cell.bench["configs"]) >= 8
