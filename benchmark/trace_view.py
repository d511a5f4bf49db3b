"""What a per-layer reader is handed: the reduced trace of the profiled
window, the compiled step, the ``Plan``, the memory statistics and the
set-up's host-clock timeline, as one ``ctx``. Also logs, on earlier
lines, the counts that repeat exactly and the where-the-time-goes
tables that ``PERF.md`` quotes, and builds the line's ``breakdown``.
"""

from __future__ import annotations

import re
import statistics
from types import SimpleNamespace

from benchmark import flops
from benchmark import trace_reduce as tr

_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_BYTES = {"pred": 1, "bf16": 2}


def _shape_bytes(text):
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        size = _BYTES.get(dtype) or int(dtype[1:]) // 8
        for d in filter(None, dims.split(",")):
            size *= int(d)
        total += size
    return total


def hlo_counts(hlo_text):
    """Counts from the compiled step that repeat exactly: collectives
    (by opcode, with the bytes of their results) and Mosaic calls."""
    collectives, mosaic = {}, 0
    for raw in hlo_text.splitlines():
        line = raw.strip()
        if not line.startswith("%") and not line.startswith("ROOT %"):
            continue
        line = line[5:] if line.startswith("ROOT ") else line
        if tr.is_mosaic_call(line):
            mosaic += 1
        op = tr.opcode(line)
        if tr.is_collective(line) and not op.endswith("-done"):
            result = line.split(" = ", 1)[1].split(" " + op + "(")[0]
            n, b = collectives.get(op, (0, 0))
            collectives[op] = (n + 1, b + _shape_bytes(result))
    return {"collectives": {op: {"calls": n, "result_bytes": b}
                            for op, (n, b) in collectives.items()},
            "tpu_custom_calls": mosaic}


def build(*, cell, asm, peak, xplane, hlo_text, timeline, memory_peak,
          baseline, untraced_step_s, log):
    trace = tr.load(xplane)
    module = hlo_text.split("HloModule ", 1)[1].split(",", 1)[0].strip()
    windows = {chip: tr.device_window(lines, module)
               for chip, lines in sorted(trace.devices.items())}
    win0 = windows[min(windows)]
    n_steps = len(win0.steps)
    attention = asm.model.attention_work(asm.per_chip_batch)

    # ------------------------------------------------ earlier lines ------
    counts = hlo_counts(hlo_text)
    log("compiled step %s: %r" % (module, counts))
    log("trace: %d chip(s), %d runs of the step on chip 0, window %.4f s, "
        "busy %.4f s" % (len(windows), n_steps, win0.window_s, win0.busy_s))
    by_category = tr.time_by(win0.ops, tr.category)
    log("device time by class, per step (ms): " + ", ".join(
        "%s %.3f" % (k, 1e3 * v / n_steps) for k, v in by_category.items()))
    kernel_s = tr.kernel_seconds(win0.ops)
    for name, (took, calls) in kernel_s.items():
        log("kernel flash %s: %d calls a step, %.1f us a call, %.3f ms a "
            "step" % (name, calls / n_steps, 1e6 * took / calls,
                      1e3 * took / n_steps))
    for direction, (ops, nbytes) in attention.items():
        least, roof = flops.roofline_seconds(ops, nbytes, peak)
        took = sum(s for name, (s, _) in kernel_s.items()
                   if tr.direction(name) == direction) / n_steps
        log("attention %s, required: %.3f ms a step at the %s roof, the "
            "flash kernels took %.3f, %.1f%% of it" % (
                direction, 1e3 * least, roof, 1e3 * took,
                100 * least / took if took else float("nan")))

    # ---------------------------------------------------- breakdown ------
    notes = tr.annotations(trace, ("dispatch", "loss_fetch", "window_edge"))
    offset = tr.host_offset(
        win0, [e for e in notes if e.name == "dispatch"])
    breakdown = {
        "device_ops": [[name, seconds] for name, seconds in list(
            tr.time_by(win0.ops, tr.instruction_name).items())[:10]],
        "idle_gaps": tr.attribute_gaps(win0, notes, offset, top=5),
    }

    return SimpleNamespace(
        cell=cell, plan=asm.plan, peak=peak, trace=trace, windows=windows,
        win0=win0, n_steps=n_steps, hlo_text=hlo_text, hlo_counts=counts,
        attention=attention, chips=cell.chips,
        step_ops=asm.model.step_ops(asm.global_batch),
        units_per_step=asm.units_per_step,
        step_device_s=statistics.median(e.seconds for e in win0.steps),
        busy_s=statistics.fmean(w.busy_s for w in windows.values()),
        window_s=statistics.fmean(w.window_s for w in windows.values()),
        memory_peak_bytes=memory_peak, timeline=timeline,
        baseline_units_per_s=baseline, untraced_step_s=untraced_step_s,
        breakdown=breakdown)
