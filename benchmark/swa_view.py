"""The attention module's device time where sliding-window and full
attention layers stand side by side, by the program's own scopes.

The same join as ``mla_view``: an ``XLA Ops`` event's instruction name
-> its ``op_name`` in the compiled step -> the segments of that scope.
Everything under a layer's ``attn`` module counts with its SELF-time
(projections, head norms, RoPE, kernels and their glue, the gate, the
output projection; forward, recomputed forward and backward). The flash
kernels' calls (Mosaic calls named ``hvd_flash_*``) are told apart by
the ``layer_<i>`` of their scope and the configuration's
``layer_types``: sliding layers' kernels skip the tiles below the
window, full layers' do not.

The gate has a scope of its own in the program (``hvd_attn_gate``,
``SCOPE_ATTN_GATE`` of ``horovod_tpu/jax/introspect.py``), but no metric
here: the compiler fuses the sigmoid and the multiply into the
neighbouring projections' fusions, a fusion carries ONE name, and no
instruction of the compiled step is left under the gate's (PERF.md
section 7 (1)).

The rooflines: the least time the chip could take for the work the
layers of one kind REQUIRE (``flops.attention_work``: forward two
products and backward five over the pairs the MASK keeps,
``flops_afmoe.window_pairs``, key/value panels ``num_key_value_heads``
wide), over the time the kernels of that kind of layer took, however
many they are.

A configuration without ``layer_types`` (every other cell's) gives
nothing: every reader returns None and never raises.
"""

from __future__ import annotations

import re

from benchmark import scope_view
from benchmark import trace_reduce as tr
from benchmark.flops_afmoe import SLIDING

# What these metrics are computed from, so spelled out here.
MODULE = "attn"
FULL = "full_attention"
_LAYER = re.compile(r"^layer_(\d+)$")


def _times(ctx):
    """{"attn": s a step, "kernels": {kind: s a step}, "layers": {kind:
    layers of the configuration}}; None for a configuration without
    ``layer_types``."""
    if not hasattr(ctx, "_swa_times"):
        try:
            from benchmark.reference.afmoe import layer_kinds
            from horovod_tpu.jax import introspect

            kinds = layer_kinds(ctx.cell.config)
            scopes = introspect.instruction_scopes(ctx.hlo_text)
            attn = 0.0
            kernels = dict.fromkeys((SLIDING, FULL), 0.0)
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                if MODULE not in path:
                    continue
                attn += own
                layer = next((m for m in map(_LAYER.match, path) if m), None)
                if layer and tr.flash_kernel(event.name):
                    kernels[kinds[int(layer.group(1))]] += event.seconds
            per_step = 1.0 / max(ctx.n_steps, 1)
            ctx._swa_times = {
                "attn": attn * 1e-9 * per_step,
                "kernels": {kind: s * per_step
                            for kind, s in kernels.items()},
                "layers": {kind: kinds.count(kind) for kind in kernels}}
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("swa view failed: %s: %s"
                            % (type(e).__name__, e))
            ctx._swa_times = None
    return ctx._swa_times


def attn_ms(ctx):
    """Milliseconds a step under the layers' ``attn`` scopes."""
    times = _times(ctx)
    return None if times is None else 1e3 * times["attn"] or None


def kernels_ms(ctx, kind):
    """Milliseconds a step in the flash kernels of the layers of
    ``kind``; None where the trace holds no such call."""
    times = _times(ctx)
    return None if times is None else 1e3 * times["kernels"][kind] or None


def kernels_roofline(ctx, kind):
    """The least time for what the configuration's layers of ``kind``
    REQUIRE, forward and backward
    (``flops_afmoe.layer_attention_work``), over the time their flash
    kernels took."""
    from benchmark import flops, flops_afmoe

    took_ms = kernels_ms(ctx, kind)
    if not took_ms:
        return None
    try:
        config, traffic = ctx.cell.config, ctx.cell.traffic
        sizes = ctx.cell.builder.sizes_of(config)
        work = flops_afmoe.layer_attention_work(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]), kind,
            **{key: sizes[key] for key in ("n_head", "n_kv", "head_dim",
                                           "window")})
        least = _times(ctx)["layers"][kind] * sum(
            flops.roofline_seconds(*work[direction], ctx.peak)[0]
            for direction in work)
        scope_view._log("flash kernels of %s layers: %.3f ms a step, %.3f "
                        "ms at the roof" % (kind, took_ms, 1e3 * least))
        return 100.0 * 1e3 * least / took_ms
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("swa roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None
