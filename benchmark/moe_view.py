"""The expert layer's device time, by the program's own scopes.

The same join as ``scope_view``: an ``XLA Ops`` event's instruction
name -> its ``op_name`` in the compiled step
(``introspect.instruction_scopes(ctx.hlo_text)``) -> the scopes the
expert layer sets inside its ``moe`` module scope (constants of
``horovod_tpu/jax/introspect.py``), each event with its SELF-time
(``scope_view.self_times``), forward and backward together, per step on
chip 0.

A program without those scopes (a commit before the layer existed, a
model without experts) gives nothing: every reader returns None and
never raises.
"""

from __future__ import annotations

from benchmark import scope_view
from benchmark import trace_reduce as tr

# The module's name in models/transformer.py and the scopes MoeMlp sets
# inside it (horovod_tpu/jax/introspect.py SCOPE_MOE_*): what these
# metrics are computed from, so spelled out here.
MODULE = "moe"
ROUTER, DISPATCH = "hvd_moe_router", "hvd_moe_dispatch"
EXPERTS, COMBINE = "hvd_moe_experts", "hvd_moe_combine"


def _times(ctx):
    """{scope segment: seconds a step} over the segments of every
    instruction under the ``moe`` module; None where there is none."""
    if not hasattr(ctx, "_moe_times"):
        try:
            from horovod_tpu.jax import introspect

            scopes = introspect.instruction_scopes(ctx.hlo_text)
            times = {}
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                if MODULE in path:
                    for segment in set(path):
                        times[segment] = times.get(segment, 0.0) + own
            per_step = 1e-9 / max(ctx.n_steps, 1)
            ctx._moe_times = {k: v * per_step
                              for k, v in times.items()} or None
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("moe view failed: %s: %s"
                            % (type(e).__name__, e))
            ctx._moe_times = None
    return ctx._moe_times


def scope_ms(ctx, *scopes):
    """Milliseconds a step under ``scopes`` (``MODULE``: the whole
    layer), summed."""
    times = _times(ctx)
    if times is None:
        return None
    return 1e3 * sum(times.get(s, 0.0) for s in scopes)


def experts_roofline(ctx):
    """The least time the chip could take for the grouped matmuls'
    operations and bytes (``flops_moe.expert_matmul_work``, forward +
    backward, every expert layer of the step) over the self-time under
    ``hvd_moe_experts``; logs which roof binds."""
    from benchmark import flops, flops_moe

    took_ms = scope_ms(ctx, EXPERTS)
    if not took_ms:
        return None
    config, traffic = ctx.cell.config, ctx.cell.traffic
    sizes = ctx.cell.builder.sizes_of(config)
    tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
    ops, nbytes = flops_moe.expert_matmul_work(
        tokens, hidden=sizes["hidden"], expert_width=sizes["expert_width"],
        n_experts=sizes["n_experts"], k=sizes["k"])
    least, roof = flops.roofline_seconds(ops, nbytes, ctx.peak)
    least *= config["num_hidden_layers"]
    scope_view._log("expert matmuls: %.3f ms a step, %.3f ms at the %s "
                    "roof" % (took_ms, 1e3 * least, roof))
    return 100.0 * 1e3 * least / took_ms
