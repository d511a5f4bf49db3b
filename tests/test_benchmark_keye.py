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


def test_the_metrics_of_the_cell():  # noqa: F811
    """``benchmark/tests/test_keye.py``'s test of this name with today's
    counts: it holds the benchmark at NINE cells and seven
    configurations with its own cell last, which a PR that adds a cell
    cannot repair (a model_config PR may not edit a file the benchmark
    already has). The checks are its own; the counts are floors (ten and
    eight at PR 45, eleven and nine at PR 49: a later cell moves
    nothing here), and the cell stands ninth."""
    import os

    from benchmark import cell as cells
    from benchmark.tests.test_keye import CELL, ROOT

    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"dsa.attn_ms", "dsa.sparse_roofline", "moe.held_roofline",
            "moe.layer_ms", "moe.experts_ms", "moe.dispatch_ms",
            "model.mfu_pct", "model.step_device_ms", "model.head_ms",
            "model.fwd_ms", "model.bwd_ms", "model.update_ms",
            "device.peak_hbm_gb", "device.idle_pct", "device.unscoped_pct",
            "launch.compile_s", "launch.cache_misses"} <= mine
    assert not mine & {
        "kernel.flash_roofline", "kernel.flash_fwd_roofline",
        "kernel.flash_dkv_roofline", "kernel.flash_dq_roofline",
        "kernel.flash_share_pct", "kernel.flash_glue_ms", "moe.shared_ms",
        "moe.experts_roofline", "mla.attn_ms", "swa.attn_ms",
        "conv.mixer_ms", "sync.collective_ms", "ssm.mixer_ms",
        "yoco.attn_ms"}
    dsa = [m for m in cell.bench["per_layer"]
           if m["name"].startswith("dsa.")]
    assert len(dsa) == 6 and {m["name"] for m in dsa} <= mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "Learned selection"
               and m["source"] == "device_trace"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in dsa)
    assert len(cell.bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) == 1
    assert len(cell.bench["configs"]) >= 8
    assert cell.bench["workloads"][8]["name"] == CELL
