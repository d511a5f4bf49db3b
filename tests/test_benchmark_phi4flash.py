"""Tier-1 runs the Phi-4-mini-flash-reasoning configuration's CPU tests
(the program against its float32 reference at tiny widths, block by
block and whole, loss and every gradient leaf; the selective scan's
kernels, interpreted, and its plain path against a loop over positions,
forward and all six gradients, at a length that is no whole number of
chunks; the published arrays' gradients with two readers each;
recomputation, and that a recomputed reader runs no scan and no key or
value projection; the kept layers' ``lambda_init``, the parameter count
and ``flops_phi4flash.py`` by hand; the defaults' case of each new
``BlockSpec`` field; the new scopes and their readers on the recorded
trace; the cell through the CPU rehearsal). Each is collected here as a
test of its own, as ``tests/test_benchmark_keye.py`` collects
Keye-VL-2.0's."""

from benchmark.tests.test_phi4flash import *  # noqa: F401,F403


def test_a_recomputed_reader_runs_no_scan_and_no_key_or_value_projection(  # noqa: F811,E501
):
    """``benchmark/tests/test_phi4flash.py``'s test of this name with
    today's count of flash calls: it holds TWELVE forward calls (four a
    differential layer), which a PR that changes the program cannot
    repair there (a perf_opt PR may not edit a file the benchmark
    already has; ROADMAP D13 (16)). The checks are its own, word for
    word; since PR 46 a differential layer runs each map over a V of
    two heads side by side, so the three layers make SIX forward calls,
    none of them again inside a ``checkpoint``."""
    import jax

    from benchmark.tests.test_phi4flash import _assembled, _work
    from horovod_tpu.jax import introspect
    from horovod_tpu.models import transformer

    cell, model, params, state, tokens = _assembled()
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.loss(p, state, tokens)[0]))(params)
    work = list(_work(jaxpr.jaxpr))
    fwd, bwd = introspect.KERNEL_SSM_SCAN_FWD, introspect.KERNEL_SSM_SCAN_BWD
    assert work.count((fwd, False)) == 2 and work.count((fwd, True)) == 0
    assert work.count((bwd, True)) == 2
    recomputed = [what for what, inside in work if inside]
    x, m, e = (2, 128, 64), 64, 128
    forward = {
        "mamba in": (x, (m, 2 * e)),
        "mamba [r, B, C]": ((2, 128, e), (e, 4 + 32)),
        "memory unit gate": (x, (m, e)),
        "k or v": (x, (m, 2, 16)),
        "q": (x, (m, 4, 16)),
        "dense up or gate": (x, (m, 96)),
        "step": ((2, 128, 4), (4, e)),
    }
    count = {name: recomputed.count(shapes)
             for name, shapes in forward.items()}
    assert count["step"] == 2, recomputed        # one a mamba layer
    assert count["mamba in"] == count["mamba [r, B, C]"] == 0
    assert count["memory unit gate"] == 0 and count["k or v"] == 0
    assert count["q"] == 0 and count["dense up or gate"] == 0
    # Six flash forward calls (two a differential layer), none again.
    flash = [what for what in work if what[0] == introspect.KERNEL_FLASH_FWD]
    assert flash == [(introspect.KERNEL_FLASH_FWD, False)] * 6
    # A cross-attention block keeps no copy of the keys and values.
    assert introspect.SAVED_FLASH_K in transformer._REMAT_KEEPS
    assert set(transformer._REMAT_KEEPS) - set(transformer._READER_KEEPS) \
        == {introspect.SAVED_FLASH_K, introspect.SAVED_FLASH_V}
    # The control: with nothing kept, every product is made again and
    # the forward scan runs a second time.
    kept = transformer._REMAT_KEEPS, transformer._READER_KEEPS
    transformer._REMAT_KEEPS = transformer._READER_KEEPS = ()
    try:
        bare = jax.make_jaxpr(jax.grad(lambda p: cell.builder.build(
            cell.config, cell.traffic).loss(p, state, tokens)[0]))(params)
    finally:
        transformer._REMAT_KEEPS, transformer._READER_KEEPS = kept
    again = [what for what, inside in _work(bare.jaxpr) if inside]
    assert again.count(fwd) == 2 and again.count(forward["mamba in"]) == 2
    assert again.count(forward["k or v"]) == 4
    assert again.count(forward["memory unit gate"]) == 1


def test_the_metrics_of_the_cell():  # noqa: F811
    """``benchmark/tests/test_phi4flash.py``'s test of this name without
    its claim to the LAST place of the lists: it holds its own cell,
    configuration and six metrics at the end of ``BENCHMARK.json``,
    which a PR that adds a cell cannot repair there (a model_config PR
    may not edit a file the benchmark already has). The checks are its
    own; the places are the ones PR 45 took (tenth cell, eighth
    configuration), which no later entry moves."""
    import os

    from benchmark import cell as cells
    from benchmark.tests.test_phi4flash import CELL, ROOT

    cell = cells.load(CELL)
    assert cell.chips == 1
    assert {m["name"] for m in cells.metrics_of(cell, "end_to_end")} == {
        "tokens_per_s", "setup_s"}
    mine = {m["name"] for m in cells.metrics_of(cell, "per_layer")}
    assert {"ssm.mixer_ms", "ssm.scan_ms", "ssm.scan_roofline", "ssm.gmu_ms",
            "yoco.attn_ms", "yoco.cross_ms", "kernel.flash_roofline",
            "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
            "kernel.flash_share_pct",
            "kernel.flash_glue_ms", "model.mfu_pct", "model.step_device_ms",
            "model.head_ms", "device.peak_hbm_gb", "device.idle_pct",
            "device.unscoped_pct", "launch.compile_s",
            "launch.cache_misses"} <= mine
    assert not mine & {"moe.layer_ms", "moe.held_roofline", "mla.attn_ms",
                       "swa.attn_ms", "conv.mixer_ms", "dsa.attn_ms",
                       "sync.collective_ms", "loop.stack_ms"}
    names = [m["name"] for m in cell.bench["per_layer"]]
    new = [m for m in cell.bench["per_layer"]
           if m["name"].startswith(("ssm.", "yoco."))]
    assert len(new) == 6 and {m["name"] for m in new} <= mine
    assert all(m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
               and m["layer"] == "State-space and shared memory"
               and os.path.exists(os.path.join(
                   ROOT, "benchmark", "layer_metrics", m["name"] + ".py"))
               for m in new)
    assert cell.bench["workloads"][9]["name"] == CELL
    assert cell.bench["configs"][7]["name"] == "phi-4-mini-flash-reasoning"
    first = names.index("ssm.mixer_ms")
    assert names[first:first + 6] == [
        "ssm.mixer_ms", "ssm.scan_ms", "ssm.scan_roofline", "ssm.gmu_ms",
        "yoco.attn_ms", "yoco.cross_ms"]
    assert len(cell.bench["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in cell.bench["workloads"]) >= 1
    assert len(cell.bench["configs"]) >= 8
