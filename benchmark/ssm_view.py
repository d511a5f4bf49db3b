"""The token mixers' device time in a model whose ``layer_types`` holds
Mamba layers, gated memory units and cross-attention beside attention
(Phi-4-mini-flash-reasoning's decoder-hybrid-decoder), by the program's
own scopes.

The same join as ``conv_view``: an ``XLA Ops`` event's instruction name
-> its ``op_name`` in the compiled step -> the segments of that scope.
Everything under a ``mamba`` layer's ``mamba`` module counts with its
SELF-time towards ``ssm.mixer_ms`` (the four projections, the taps, the
step, the scan, the gate; forward, recomputed forward and backward);
what of it stands under ``hvd_ssm_scan`` (``SCOPE_SSM_SCAN`` of
``horovod_tpu/jax/introspect.py``: the two kernels of
``ops/pallas_scan.py`` and the pads, casts and transposes round them)
towards ``ssm.scan_ms``, and ``ssm.scan_roofline`` is the least time for
the bytes and operations the scans must move
(``flops_phi4flash.scan_work``, a ``mamba`` layer each) over it.
Everything under a ``memory_unit`` layer's ``gmu`` module counts towards
``ssm.gmu_ms``; everything under an attention layer's ``attn`` module
(window, full and cross alike: projections, the flash kernels and their
glue, the differential subtraction and norm, the output projection)
towards ``yoco.attn_ms``, and the ``cross_attention`` layers' part of it
towards ``yoco.cross_ms``.

A configuration without ``layers_kept`` or without a ``mamba`` layer
(every other cell's), a program without the scopes (the parent's), a
step whose compiler left no instruction under one: every reader returns
None and never raises.
"""

from __future__ import annotations

import re

from benchmark import scope_view
from benchmark import trace_reduce as tr

# What these metrics are computed from, so spelled out here.
MAMBA, MEMORY_UNIT, CROSS = "mamba", "memory_unit", "cross_attention"
MIXER, GMU, ATTN, SCAN = "mamba", "gmu", "attn", "hvd_ssm_scan"
PARTS = ("mixer", "scan", "gmu", "attn", "cross")
_LAYER = re.compile(r"^layer_(\d+)$")


def _times(ctx):
    """{part: seconds a step}; None for a configuration without
    ``mamba`` layers."""
    if not hasattr(ctx, "_ssm_times"):
        try:
            from benchmark.reference.phi4flash import layer_kinds
            from horovod_tpu.jax import introspect

            # A configuration without ``layers_kept`` has no such key.
            kinds = layer_kinds(ctx.cell.config)
            if MAMBA not in kinds:
                raise LookupError("no mamba layer in this configuration")
            scopes = introspect.instruction_scopes(ctx.hlo_text)
            times = dict.fromkeys(PARTS, 0.0)
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                layer = next((m for m in map(_LAYER.match, path) if m), None)
                if layer is None:
                    continue
                rest = path[path.index(layer.group(0)) + 1:]
                kind = kinds[int(layer.group(1))]
                if kind == MAMBA:
                    if MIXER in rest:
                        times["mixer"] += own
                        if SCAN in rest:
                            times["scan"] += own
                elif kind == MEMORY_UNIT:
                    if GMU in rest:
                        times["gmu"] += own
                elif ATTN in rest:
                    times["attn"] += own
                    if kind == CROSS:
                        times["cross"] += own
            per_step = 1e-9 / max(ctx.n_steps, 1)
            ctx._ssm_times = {k: v * per_step for k, v in times.items()}
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("ssm view: nothing to read: %s: %s"
                            % (type(e).__name__, e))
            ctx._ssm_times = None
    return ctx._ssm_times


def part_ms(ctx, part):
    """Milliseconds a step under ``part`` (one of ``PARTS``); None where
    the trace holds nothing there."""
    times = _times(ctx)
    return None if times is None else 1e3 * times[part] or None


def scan_roofline(ctx):
    """The least time for the selective scans' bytes and operations in
    the step's ``mamba`` layers, forward + backward, over ``scan``'s
    self-time; logs which roof binds."""
    took_ms = part_ms(ctx, "scan")
    if not took_ms:
        return None
    try:
        from benchmark import flops, flops_phi4flash
        from benchmark.reference.phi4flash import layer_kinds

        config, traffic = ctx.cell.config, ctx.cell.traffic
        tokens = int(traffic["per_chip_batch"]) * int(traffic["seq_len"])
        least, roof = flops.roofline_seconds(
            *flops_phi4flash.scan_work(
                tokens, config["mamba_expand"] * config["hidden_size"],
                config["mamba_d_state"]), ctx.peak)
        least *= layer_kinds(config).count(MAMBA)
        scope_view._log("selective scans: %.3f ms a step, %.3f ms at the "
                        "%s roof" % (took_ms, 1e3 * least, roof))
        return 100.0 * 1e3 * least / took_ms
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("ssm.scan_roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None
