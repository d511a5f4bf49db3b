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


def test_the_metrics_of_the_cell():  # noqa: F811
    """``benchmark/tests/test_trinity.py``'s test of this name with
    today's lists: it holds the five ``swa.*`` metrics to THIS cell
    alone, and since PR 53 ``smallthinker-s8192-ep4-c1`` (window and full
    layers at 28 over 4 heads) is appended to each of their ``workloads``,
    which a PR that adds a cell cannot repair in the original (a
    model_config PR may not edit a file the benchmark already has). The
    checks are its own; the lists START with this cell."""
    from benchmark import cell as cells
    from benchmark.tests.test_trinity import CELL

    cell = cells.load(CELL)
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"swa.attn_ms", "swa.window_ms", "swa.full_ms",
            "swa.window_roofline", "swa.full_roofline", "moe.held_roofline",
            "moe.shared_ms", "kernel.flash_roofline",
            "kernel.flash_fwd_roofline", "model.mfu_pct",
            "device.unscoped_pct", "launch.compile_s"} <= mine
    assert not mine & {"mla.attn_ms", "mla.latent_ms",
                       "moe.experts_roofline", "sync.collective_ms",
                       "moe.preroute_ms"}
    swa = [m for m in cell.bench["per_layer"] if m["name"].startswith("swa.")]
    assert len(swa) == 5      # the gate fuses away: no ``swa.gate_ms``
    assert all(m["workloads"][0] == CELL and m["moves"] == "tokens_per_s"
               and m["layer"] == "Attention pattern" for m in swa)
