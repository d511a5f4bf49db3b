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
``tests/test_benchmark_lfm2.py`` collects LFM2-8B-A1B's.

Three tests of that file are restated below under their own names,
each with every assertion of the original: one holds the benchmark at
nine cells, two name the masked backward's kernels as they were before
PR 52 (``hvd_dsa_dkv`` + ``hvd_dsa_dq``, now ONE ``hvd_dsa_bwd``). Only a
``benchmark`` PR may edit the originals (lines 602, 627 and 965 there);
once one has, the two restatements go and the star import collects the
originals again."""

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


def test_the_counters_of_a_sparse_model():  # noqa: F811
    """``benchmark/tests/test_keye.py``'s test of this name, every
    assertion, with the masked backward's kernel as PR 52 left it: ONE
    ``hvd_dsa_bwd`` where ``hvd_dsa_dkv`` + ``hvd_dsa_dq`` were, which
    trace nothing at these widths."""
    import jax

    from benchmark.tests.test_keye import SPARSE, _assembled
    from horovod_tpu.models import transformer
    from horovod_tpu.ops import pallas_attention

    cell, model, params, state, tokens = _assembled("float32")
    tiles = {(kernel, kind): pallas_attention._M_TILES.labels(
        kernel=kernel, kind=kind) for kernel in (
            "hvd_dsa_fwd", "hvd_dsa_bwd", "hvd_dsa_dkv", "hvd_dsa_dq",
            "hvd_flash_fwd")
        for kind in ("learned", "skipped", "full", "edge")}

    def read():
        return (
            {k: transformer._M_ATTN_LAYERS.labels(kind=k).get()
             for k in (SPARSE, "full_attention")},
            {k: transformer._M_DSA_PAIRS.labels(kind=k).get()
             for k in ("causal", "kept")},
            {k: v.get() for k, v in tiles.items()},
            transformer._M_REMAT_BLOCKS.labels(keeps="flash+products").get())

    before = read()
    jax.eval_shape(jax.grad(lambda p: model.loss(p, state, tokens)[0]),
                   params)
    after = read()
    layers = {k: after[0][k] - before[0][k] for k in after[0]}
    assert layers[SPARSE] > 0 and layers["full_attention"] == 0
    assert layers[SPARSE] % 2 == 0
    traces = layers[SPARSE] // 2
    pairs = {k: after[1][k] - before[1][k] for k in after[1]}
    assert pairs["causal"] == traces * 2 * 2 * (128 * 129 // 2)
    assert pairs["kept"] == traces * 2 * 2 * sum(
        min(t + 1, 32) for t in range(128))
    moved = {k: after[2][k] - before[2][k] for k in tiles}
    for kernel in ("hvd_dsa_fwd", "hvd_dsa_bwd"):
        assert moved[kernel, "learned"] > 0, kernel      # one 128 x 128 tile
        assert moved[kernel, "full"] == moved[kernel, "edge"] == 0
    assert not any(moved[kernel, kind] for kernel in (
        "hvd_dsa_dkv", "hvd_dsa_dq") for kind in (
        "learned", "skipped", "full", "edge"))
    assert not any(moved["hvd_flash_fwd", kind] for kind in (
        "learned", "full", "edge"))
    assert after[3] - before[3] == 2 * traces
    assert pallas_attention._Tiles(512, 512, True, 8192, 8192, None,
                                   True).counts() == {
        "learned": 136, "skipped": 120}
    assert pallas_attention._Tiles(512, 512, True, 8192, 8192).counts() == {
        "full": 120, "edge": 16, "skipped": 120}


def test_the_scope_constants_are_what_the_layers_set():  # noqa: F811
    """``benchmark/tests/test_keye.py``'s test of this name, every
    assertion; the lowered tiny model holds ``hvd_dsa_fwd`` and
    ``hvd_dsa_bwd`` under ``attn/hvd_flash`` and neither of the pair
    (whose names stay constants of ``introspect``: past the VMEM cap
    they run)."""
    import jax

    from benchmark import dsa_view
    from benchmark.tests.test_keye import _assembled
    from horovod_tpu.jax import introspect

    assert (introspect.SCOPE_DSA_INDEX, introspect.SCOPE_DSA_SELECT) == (
        dsa_view.INDEX, dsa_view.SELECT) == (
        "hvd_dsa_index", "hvd_dsa_select")
    assert sorted(dsa_view.MASKED + k for k in ("fwd", "dkv", "dq")) == sorted((
        introspect.KERNEL_DSA_FWD, introspect.KERNEL_DSA_DKV,
        introspect.KERNEL_DSA_DQ)) == [
        "hvd_dsa_dkv", "hvd_dsa_dq", "hvd_dsa_fwd"]
    assert dsa_view.MASKED + "bwd" == introspect.KERNEL_DSA_BWD
    assert dsa_view.MASKED + dsa_view.CHOOSE \
        == introspect.KERNEL_DSA_CHOOSE == "hvd_dsa_choose"
    assert introspect.SAVED_FLASH_SELECT == "hvd_flash_select"
    cell, model, params, state, tokens = _assembled("float32")
    grad = jax.grad(lambda p: model.loss(p, state, tokens)[0])
    text = jax.jit(grad).lower(params).as_text(debug_info=True)
    for name in ("layer_0/attn/hvd_dsa_index", "layer_1/attn/hvd_dsa_index",
                 "layer_0/attn/hvd_dsa_select", "layer_1/attn/q_norm",
                 "hvd_dsa_index/index_k_norm", "layer_1/attn/rope",
                 "layer_0/attn/hvd_flash/hvd_dsa_fwd",
                 "attn/hvd_flash/hvd_dsa_bwd",
                 "layer_0/moe/hvd_moe_router"):
        assert name in text, name
    for name in ("hvd_dsa_dkv", "hvd_dsa_dq",
                 "hvd_flash_fwd", "hvd_flash_bwd", "hvd_flash_dkv",
                 "hvd_flash_dq", "hvd_moe_shared", "hvd_attn_gate", "/mlp/",
                 "/conv/", "rematted_computation/layer_0/attn/hvd_dsa",
                 "rematted_computation/layer_1/attn/hvd_dsa"):
        assert name not in text, name
