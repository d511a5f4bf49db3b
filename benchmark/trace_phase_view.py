"""The trace phase of the measured step, from inside.

``launch.step_trace_s`` is ONE span: jax running the program's Python to
make the step's jaxpr. What that Python was doing is in the ``trace/*``
spans the program files while a compile phase is open
(``horovod_tpu/utils/timeline.py`` ``trace_span``): ``trace/block``
(a decoder block), ``trace/experts`` (an expert layer), ``trace/loop_pass``
and ``trace/readout`` (a looped stack's pass and its loss),
``trace/kernel`` (ONE Pallas kernel body jax traced, with the call's
``kernel`` name), ``trace/sync`` and ``trace/update`` (the gradient sync
and the optimizer's update). Read here are those that lie inside the
NEWEST ``compile/trace`` span of the step's function
(``launch_view.step_spans``; the check's earlier trace of the same name
is left out the same way), as seconds COVERED, so that a span nested in
another counts once:

- ``kernels``: what the ``trace/kernel`` spans cover;
- ``model``: what the blocks, expert layers, passes and readout cover,
  less the kernels: the model's own Python, flax's lifting and
  ``nn.remat`` round the blocks included;
- ``update``: what ``trace/sync`` and ``trace/update`` cover (less
  anything above, which they never hold);
- ``rest``: the step's trace less everything any ``trace/*`` span
  covers: jax's differentiation and transposition, the builder's loss,
  ``shard_map``.

The four add up to ``launch.step_trace_s``. ``bodies`` is the number of
``trace/kernel`` spans there: a jitted callee files one on a
tracing-cache miss only, so it is the count of kernel bodies traced.

A program without ``trace/*`` spans (a commit before they existed) gives
nothing, and so does a log that dropped a span (``SpanLog.dropped``: a
partial sum is not a reading): every reader returns None and never
raises.
"""

from __future__ import annotations

import collections

from benchmark import launch_view, scope_view

# What these metrics are computed from, so spelled out here.
TRACE = "trace/"
KERNEL = "kernel"
MODEL = ("block", "experts", "loop_pass", "readout")
UPDATE = ("sync", "update")


def dropped(ctx):
    """Spans the program's log lost off its old end (a ``ctx`` made by
    hand brings its own as ``ctx.launch_dropped``); None where the log
    does not say."""
    found = getattr(ctx, "launch_dropped", None)
    if found is None:
        from horovod_tpu.utils import timeline

        found = getattr(timeline.LAUNCH_LOG, "dropped", None)
    return found


def _inside(ctx):
    """(the step's ``compile/trace`` span, {part: the ``trace/<part>``
    spans inside it}); None where there are none."""
    trace = (launch_view.step_spans(ctx) or {}).get("trace")
    if trace is None:
        return None
    by_part = collections.defaultdict(list)
    for s in launch_view.spans(ctx):
        if s["name"].startswith(TRACE) and trace["start"] <= s["start"] \
                and s["end"] <= trace["end"]:
            by_part[s["name"][len(TRACE):]].append(s)
    return (trace, by_part) if by_part else None


def _edges(by_part, parts):
    return [(s["start"], s["end"]) for part in parts for s in by_part[part]]


def _describe(trace_s, seconds, by_part, in_log):
    def took(*parts, but=()):
        return launch_view.covered(_edges(by_part, parts),
                                   but=_edges(by_part, but))

    by_kernel = collections.defaultdict(list)
    for s in by_part[KERNEL]:
        by_kernel[s["args"].get("kernel", "?")].append(s["end"] - s["start"])
    each = ", ".join("%s %.3f x%d" % (part, took(part), len(by_part[part]))
                     for part in MODEL + UPDATE if by_part[part])
    lifting = ""
    if by_part["loop_pass"]:
        lifting = "; loop_pass less its blocks %.3f" % took(
            "loop_pass", but=("block",))
    return (
        "trace phase: the step's trace %.3f s = kernels %.3f + model %.3f + "
        "update %.3f + rest %.3f; each part with what nests in it: %s%s; "
        "%d kernel bodies: %s; %d trace/* spans in the step's trace, %d in "
        "the log, dropped 0" % (
            trace_s, seconds["kernels"], seconds["model"], seconds["update"],
            seconds["rest"], each or "none", lifting, len(by_part[KERNEL]),
            ", ".join("%s %.3f x%d" % (name, sum(took_s), len(took_s))
                      for name, took_s in sorted(by_kernel.items()))
            or "none",
            sum(len(found) for found in by_part.values()), in_log))


def _read(ctx):
    found = _inside(ctx)
    if found is None:
        return None
    trace, by_part = found
    lost = dropped(ctx)
    if lost != 0:
        scope_view._log("trace phase: the span log dropped %r spans: a "
                        "partial sum is not a reading" % (lost,))
        return None
    kernels = _edges(by_part, (KERNEL,))
    model = _edges(by_part, MODEL)
    trace_s = trace["end"] - trace["start"]
    seconds = {
        "kernels": launch_view.covered(kernels),
        "model": launch_view.covered(model, but=kernels),
        "update": launch_view.covered(_edges(by_part, UPDATE),
                                      but=kernels + model)}
    seconds["rest"] = trace_s - sum(seconds.values())
    in_log = sum(s["name"].startswith(TRACE) for s in launch_view.spans(ctx))
    scope_view._log(_describe(trace_s, seconds, by_part, in_log))
    return dict(seconds, bodies=len(kernels))


def parts(ctx):
    """{"kernels", "model", "update", "rest": seconds of the step's
    trace, "bodies": kernel bodies traced in it}; None where the log has
    no ``trace/*`` span there or dropped any."""
    if not hasattr(ctx, "_trace_phase"):
        try:
            ctx._trace_phase = _read(ctx)
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("trace phase: nothing to read: %s: %s"
                            % (type(e).__name__, e))
            ctx._trace_phase = None
    return ctx._trace_phase


def part(ctx, name):
    """One entry of ``parts``; None where there is no reading."""
    found = parts(ctx)
    return None if found is None else found[name]
