"""The token mixers' device time in a model whose ``layer_types`` holds
gated short-convolution layers beside attention layers, by the program's
own scopes.

The same join as ``swa_view``: an ``XLA Ops`` event's instruction name
-> its ``op_name`` in the compiled step -> the segments of that scope.
Everything under a ``conv`` layer's ``conv`` module counts with its
SELF-time towards ``conv.mixer_ms`` (the two projections, the gates, the
taps; forward, recomputed forward and backward), everything under an
attention layer's ``attn`` module towards ``conv.attn_ms`` (projections,
head norms, RoPE, the flash kernels and their glue, the output
projection), so that the two kinds' cost reads side by side. The gates
and taps have a scope of their own inside the module
(``hvd_conv_gate``, ``SCOPE_CONV_GATE`` of
``horovod_tpu/jax/introspect.py``): ``conv.gate_ms`` is the self-time
under it, and ``conv.gate_roofline`` the least time for the bytes and
operations those passes must move (``flops_lfm2.conv_gate_work``, a
``conv`` layer each) over it.

A configuration whose layers hold no ``conv`` (every other cell's), a
program without the scopes, a step whose compiler left no instruction
under one: every reader returns None and never raises.
"""

from __future__ import annotations

import re

from benchmark import scope_view
from benchmark import trace_reduce as tr

# What these metrics are computed from, so spelled out here.
CONV, MIXER, ATTN, GATE = "conv", "conv", "attn", "hvd_conv_gate"
_LAYER = re.compile(r"^layer_(\d+)$")


def _times(ctx):
    """{"mixer" | "attn" | "gate": seconds a step}; None for a
    configuration without ``conv`` layers."""
    if not hasattr(ctx, "_conv_times"):
        try:
            from benchmark.reference.lfm2_moe import layer_kinds
            from horovod_tpu.jax import introspect

            # A configuration without ``layer_types`` has no such key.
            kinds = layer_kinds(ctx.cell.config)
            if CONV not in kinds:
                raise LookupError("no conv layer in this configuration")
            scopes = introspect.instruction_scopes(ctx.hlo_text)
            times = dict.fromkeys(("mixer", "attn", "gate"), 0.0)
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                layer = next((m for m in map(_LAYER.match, path) if m), None)
                if layer is None:
                    continue
                rest = path[path.index(layer.group(0)) + 1:]
                if kinds[int(layer.group(1))] == CONV:
                    if MIXER in rest:
                        times["mixer"] += own
                        if GATE in rest:
                            times["gate"] += own
                elif ATTN in rest:
                    times["attn"] += own
            per_step = 1e-9 / max(ctx.n_steps, 1)
            ctx._conv_times = {k: v * per_step for k, v in times.items()}
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("conv view: nothing to read: %s: %s"
                            % (type(e).__name__, e))
            ctx._conv_times = None
    return ctx._conv_times


def part_ms(ctx, part):
    """Milliseconds a step under ``part`` ('mixer', 'attn', 'gate');
    None where the trace holds nothing there."""
    times = _times(ctx)
    return None if times is None else 1e3 * times[part] or None


def gate_roofline(ctx):
    """The least time for the gates' and taps' bytes and operations in
    the step's ``conv`` layers, forward + backward, over ``gate``'s
    self-time; logs which roof binds."""
    from benchmark import flops, flops_lfm2
    from benchmark.reference.lfm2_moe import layer_kinds

    took_ms = part_ms(ctx, "gate")
    if not took_ms:
        return None
    try:
        config, traffic = ctx.cell.config, ctx.cell.traffic
        tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
        least, roof = flops.roofline_seconds(
            *flops_lfm2.conv_gate_work(tokens, config["hidden_size"],
                                       config["conv_L_cache"]), ctx.peak)
        least *= layer_kinds(config).count(CONV)
        scope_view._log("conv gates and taps: %.3f ms a step, %.3f ms at "
                        "the %s roof" % (took_ms, 1e3 * least, roof))
        return 100.0 * 1e3 * least / took_ms
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("conv.gate_roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None
