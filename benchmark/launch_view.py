"""The launch, from the program's own span log and counters.

``horovod_tpu`` keeps what its launch was made of: ``import``, ``init``,
``plan`` and ``plan/apply`` spans of its own, and, from jax's events,
``compile/trace``, ``compile/lower`` and ``compile/backend`` spans with
the function's name and what the persistent cache did
(``hvd.launch_spans()``; ``horovod_tpu/utils/timeline.py`` ``SpanLog``,
``utils/compile_cache.py`` ``CompileListener``). Their ``start`` and
``end`` are on ``time.time()``, the clock ``setup_s`` is taken on. The
counter read here is ``hvd_compiles_total{cache}``.

A program without the log (a commit before it existed) gives nothing:
every reader returns None and never raises.
"""

from __future__ import annotations

import os
import time

from benchmark import scope_view

# What these metrics are computed from, so spelled out here.
PROGRAM_SPANS = ("import", "init", "plan", "plan/apply")
COMPILE = "compile/"
PHASES = ("trace", "lower", "backend")
BASELINE = "hvd_bench_baseline"   # run.py's single-worker baseline step
COMPILES = "hvd_compiles_total"


def process_start():
    """``run.py``'s reading of the same name: the wall-clock time this
    process was started at, by the kernel; None where it does not tell."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return None


def spans(ctx):
    """The closed spans of the program's log, oldest first (a ``ctx``
    made by hand brings its own as ``ctx.launch_spans``); None where the
    program keeps none."""
    if not hasattr(ctx, "_launch_spans"):
        found = getattr(ctx, "launch_spans", None)
        if found is None:
            try:
                import horovod_tpu

                found = horovod_tpu.launch_spans()
            except Exception as e:   # noqa: BLE001 - a reader never raises
                scope_view._log("launch view: no span log: %s: %s"
                                % (type(e).__name__, e))
        ctx._launch_spans = None if found is None else [
            s for s in found if s["end"] is not None]
    return ctx._launch_spans


def covered(intervals, but=()):
    """Seconds the ``(start, end)`` intervals cover together, less what
    the intervals of ``but`` cover of that."""
    edges = [(t, kind, step) for kind, group in ((0, intervals), (1, but))
             for start, end in group for t, step in ((start, 1), (end, -1))]
    depth, total, last = [0, 0], 0.0, None
    for t, kind, step in sorted(edges):
        if depth[0] and not depth[1]:
            total += t - last
        depth[kind] += step
        last = t
    return total


def _edges(found):
    return [(s["start"], s["end"]) for s in found]


def program_s(ctx):
    """Seconds covered by the program's own spans that ended before the
    first device array (``process_start() + launch.init_s``, with a
    clock tick of room)."""
    found = spans(ctx)
    if found is None:
        return None
    began = process_start()
    first_array = float("inf") if began is None \
        else began + ctx.timeline["init_s"] + 0.05
    own = [s for s in found
           if s["name"] in PROGRAM_SPANS and s["end"] <= first_array]
    return covered(_edges(own)) if own else None


def step_fun_name(ctx):
    """The measured step's function, as jax's compile events name it:
    the compiled module's name less ``jit_``."""
    module = ctx.hlo_text.split("HloModule ", 1)[1].split(",", 1)[0].strip()
    return module[len("jit_"):] if module.startswith("jit_") else module


def step_spans(ctx):
    """{phase: the NEWEST ``compile/<phase>`` span of the step's
    function} (an earlier program may share the name: the check's
    ``sgd_step`` does); None where the log has none."""
    found = spans(ctx)
    if found is None:
        return None
    try:
        name, newest = step_fun_name(ctx), {}
    except (AttributeError, IndexError):   # no compiled step: no reader raises
        return None
    for s in found:
        if s["name"].startswith(COMPILE) \
                and s["args"].get("fun_name") == name:
            newest[s["name"][len(COMPILE):]] = s
    return newest or None


def step_phase_s(ctx, phase):
    newest = step_spans(ctx)
    if newest is None or phase not in newest:
        return None
    return newest[phase]["end"] - newest[phase]["start"]


def setup_compile_s(ctx):
    """Seconds covered by every ``compile/*`` span but the step's own
    three and the baseline step's."""
    found = spans(ctx)
    if found is None:
        return None
    own = {s["id"] for s in (step_spans(ctx) or {}).values()}
    rest, apart = [], []
    for s in found:
        if s["name"].startswith(COMPILE):
            (apart if s["id"] in own or s["args"].get("fun_name") == BASELINE
             else rest).append(s)
    return covered(_edges(rest), but=_edges(apart)) if rest else None


def cache_misses(ctx):
    """``hvd_compiles_total{cache="miss"}`` now: programs the process
    asked the persistent cache for and had to compile."""
    if spans(ctx) is None:
        return None
    counted = getattr(ctx, "launch_counters", None)
    if counted is None:
        try:
            from horovod_tpu.utils import metrics

            if metrics.REGISTRY.get(COMPILES) is None:
                return None
            return metrics.value(COMPILES, cache="miss") or 0.0
        except Exception as e:   # noqa: BLE001 - a reader never raises
            scope_view._log("launch view: no counter: %s: %s"
                            % (type(e).__name__, e))
            return None
    return counted.get("miss", 0.0)
