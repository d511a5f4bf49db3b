"""A looped stack's device time (``total_ut_steps`` > 1 in the
configuration: ONE stack of blocks applied several times a step,
Ouro-2.6B), by the program's own scopes.

The same join as ``ssm_view``: an ``XLA Ops`` event's instruction name
-> its ``op_name`` in the compiled step -> the segments of that scope.
Everything under a ``hvd_loop_pass_<t>`` scope (``SCOPE_LOOP_PASS`` of
``horovod_tpu/jax/introspect.py``: the blocks of one pass and the norm
that closes it; forward, recomputed forward and backward) counts with
its SELF-time towards ``loop.stack_ms``; what of it also carries a
recomputation's name (``rematted_computation`` among the segments: the
forward made AGAIN in the backward pass, which is what the rule that
chooses recomputation by pass costs; a bare ``checkpoint`` segment
marks the first forward and the backward too) towards
``loop.recompute_ms``. Everything under ``hvd_loop_readout`` (each
pass's output projection, itself under ``logits``, and its cross
entropy, forward and backward) counts towards ``loop.readout_ms``, and
``loop.readout_roofline`` is the least time for the readouts' required
operations (``flops_ouro.readout_ops``) at the chip's peak over it.
Everything under ``hvd_loop_exit`` (the gate, the exit distribution, its
entropy and the weighted sum) counts towards ``loop.exit_ms``.

A configuration without ``total_ut_steps`` (every other cell's), a
program without the scopes (the parent's), a step whose compiler left
no instruction under one: every reader returns None and never raises.
"""

from __future__ import annotations

import re

from benchmark import scope_view
from benchmark import trace_reduce as tr

# What these metrics are computed from, so spelled out here. A scope
# that stands outermost inside a transform is wrapped by it
# (``jvp(hvd_loop_readout)``, ``transpose(jvp(hvd_loop_exit))``), so
# the names are looked for INSIDE a segment.
PASS = re.compile(r"(?<![\w.])hvd_loop_pass_\d+(?![\w.])")
READOUT = re.compile(r"(?<![\w.])hvd_loop_readout(?![\w.])")
EXIT = re.compile(r"(?<![\w.])hvd_loop_exit(?![\w.])")
REMADE = "rematted_computation"
PARTS = ("stack", "recompute", "readout", "exit")


def part_of(path):
    """The parts (of ``PARTS``) an instruction with these scope
    segments counts towards."""
    def under(name):
        return any(name.search(segment) for segment in path)

    if under(PASS):
        return ("stack", "recompute") if REMADE in path else ("stack",)
    if under(READOUT):
        return ("readout",)
    if under(EXIT):
        return ("exit",)
    return ()


def _times(ctx):
    """{part: seconds a step}; None for a configuration that loops
    nothing."""
    if not hasattr(ctx, "_loop_times"):
        try:
            from horovod_tpu.jax import introspect

            if int(ctx.cell.config["total_ut_steps"]) < 2:
                raise LookupError("one pass: no loop in this configuration")
            scopes = introspect.instruction_scopes(ctx.hlo_text)
            times = dict.fromkeys(PARTS, 0.0)
            for event, own in zip(ctx.win0.ops,
                                  scope_view.self_times(ctx.win0.ops)):
                path = scope_view._path(
                    scopes.get(tr.instruction_name(event.name), ""))
                for part in part_of(path):
                    times[part] += own
            per_step = 1e-9 / max(ctx.n_steps, 1)
            ctx._loop_times = {k: v * per_step for k, v in times.items()}
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("loop view: nothing to read: %s: %s"
                            % (type(e).__name__, e))
            ctx._loop_times = None
    return ctx._loop_times


def part_ms(ctx, part):
    """Milliseconds a step under ``part`` (one of ``PARTS``); None where
    the trace holds nothing there."""
    times = _times(ctx)
    return None if times is None else 1e3 * times[part] or None


def readout_roofline(ctx):
    """The least time for the readouts' required operations, forward +
    backward, at the chip's peak, over ``readout``'s self-time."""
    took_ms = part_ms(ctx, "readout")
    if not took_ms:
        return None
    try:
        from benchmark import flops_ouro

        config, traffic = ctx.cell.config, ctx.cell.traffic
        ops = flops_ouro.readout_ops(
            int(traffic["per_chip_batch"]), int(traffic["seq_len"]),
            vocab=config["vocab_size"], hidden=config["hidden_size"],
            passes=config["total_ut_steps"])
        least = ops / ctx.peak["bf16_flops"]
        scope_view._log("looped readouts: %.3f ms a step, %.3f ms at the "
                        "compute roof" % (took_ms, 1e3 * least))
        return 100.0 * 1e3 * least / took_ms
    except Exception as e:   # noqa: BLE001 - a reader never raises
        scope_view._log("loop.readout_roofline failed: %s: %s"
                        % (type(e).__name__, e))
        return None
