"""The HELD experts' grouped matmuls' share of their roofline: the
least time the chip could take for their operations and bytes at the
balanced load (``flops_glm.held_expert_matmul_work``: forward and two
backward matmuls for each forward one, no recomputation, every expert
layer of the step) over the self-time under ``hvd_moe_experts``. None
for a configuration whose expert layer holds all it routes over
(``moe.experts_roofline`` is that one's)."""

from benchmark import flops, flops_glm, moe_view, scope_view


def read(ctx):
    try:
        config, traffic = ctx.cell.config, ctx.cell.traffic
        routed = config.get("experts_routed_over")
        took_ms = moe_view.scope_ms(ctx, moe_view.EXPERTS)
        if not routed or not took_ms:
            return None
        tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
        sizes = ctx.cell.builder.sizes_of(config)
        ops, nbytes = flops_glm.held_expert_matmul_work(tokens, **{
            key: sizes[key] for key in ("hidden", "expert_width", "k",
                                        "held", "routed")})
        least, roof = flops.roofline_seconds(ops, nbytes, ctx.peak)
        least *= config["num_hidden_layers"] - config["first_k_dense_replace"]
        scope_view._log("held expert matmuls: %.3f ms a step, %.3f ms at "
                        "the %s roof" % (took_ms, 1e3 * least, roof))
        return 100.0 * 1e3 * least / took_ms
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("moe.held_roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None
