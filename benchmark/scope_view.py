"""The step's device time by program scope.

An ``XLA Ops`` event of the trace carries its instruction's HLO text
without metadata, so the join goes: event -> instruction name
(``trace_reduce.instruction_name``) -> ``op_name`` of that instruction
in the compiled step (``introspect.instruction_scopes(ctx.hlo_text)``)
-> ``(phase, part)``. Each event counts with its SELF-time: its
duration minus what events nested inside it cover (a ``while`` holds
the instructions of its body), so that the self-times add up to the
time the chip was busy. ``table(ctx)`` checks that they do.

Phases: ``forward``, ``backward`` (a ``transpose(`` in the path),
``sync`` (``hvd_sync``), ``update`` (``hvd_update``, and the step's
top-level arithmetic: ``optax.apply_updates``, which XLA fuses with the
optimizer), ``unscoped``. Parts, disjoint: ``attn`` (the attention
module outside ``hvd_flash``), ``flash_kernel`` (the Mosaic calls named
``hvd_flash_*``), ``flash_glue`` (the rest of ``hvd_flash``), ``mlp``,
``norm``, ``conv``, ``bn``, ``head`` (``embed``, ``logits``, the classifier and
the loss, which sits outside any module), ``other`` (a block's residual
adds, pooling), ``sync_collective``, ``sync_pack``, ``update``,
``unscoped``.

A fusion carries one name, its root's or its matmul's: where XLA fuses
a weight's optimizer update into the matmul that makes its gradient, or
a batch norm into a convolution, the whole fusion goes to the matmul's
scope (PERF.md, PR 24).

A program without the scopes (a commit before they existed) gives no
table: every reader of it returns None and never raises.
"""

from __future__ import annotations

import re
import sys
from collections import defaultdict
from types import SimpleNamespace

from benchmark import trace_reduce as tr

PHASES = ("forward", "backward", "sync", "update", "unscoped")
TOLERANCE = 0.01   # self-times against the busy union

_WRAPPER = re.compile(r"^(jit|pjit|pmap|shard_map|xmap)\b")
_TRANSFORM = re.compile(r"^(jvp|transpose|vmap|remat|checkpoint|custom_\w+)\(")
# A module's kind, from the name flax gave its scope (innermost first).
_MODULE_PARTS = (
    ("attn", re.compile(r"^(attn|attention)\w*$", re.I)),
    ("mlp", re.compile(r"^(mlp|moe)\w*$", re.I)),
    ("bn", re.compile(r"^(BatchNorm_\d+|bn_\w+|norm_proj)$")),
    ("norm", re.compile(r"^(ln\w*|LayerNorm_\d+|\w*norm\w*)$", re.I)),
    ("conv", re.compile(r"^conv\w*$", re.I)),
    ("head", re.compile(r"^(embed|logits|Dense_\d+)$")),
)


def _path(scope):
    """The segments of an ``op_name`` (the first, where the compiler
    joined several with ``;``) below the ``jit``/``shard_map`` wrappers."""
    path = [s for s in scope.split(";")[0].split("/") if s]
    while path and _WRAPPER.match(path[0]):
        path.pop(0)
    return path


def classify(scope, event_name=""):
    """``(phase, part)`` of one instruction: ``scope`` is its ``op_name``
    (own or inherited), ``event_name`` its HLO text, which tells a
    collective from the copies beside it and a flash kernel (a Mosaic
    call named ``hvd_flash_*``) from the slices that feed it."""
    path = _path(scope)
    if not path:
        return "unscoped", "unscoped"
    if "hvd_sync" in path:
        return "sync", ("sync_collective" if tr.is_collective(event_name)
                        else "sync_pack")
    transforms = [s for s in path if _TRANSFORM.match(s)]
    modules = [s for s in path[:-1]
               if not _TRANSFORM.match(s) and not _WRAPPER.match(s)]
    if "hvd_update" in path or not (transforms or modules):
        return "update", "update"
    phase = ("backward" if any(s.startswith("transpose(") for s in transforms)
             else "forward")
    if "hvd_flash" in modules:
        return phase, ("flash_kernel" if tr.flash_kernel(event_name)
                       else "flash_glue")
    for module in reversed(modules):
        for part, pattern in _MODULE_PARTS:
            if pattern.match(module):
                return phase, part
    # Differentiated, and inside no module: the builder's loss function
    # (``jvp()``, ``transpose(jvp())``, ``jvp(jit(take_along_axis))``).
    named = [t for t in transforms if re.search(r"\(([A-Z]\w*)\)", t)]
    return phase, "other" if named else "head"


def self_times(events):
    """Nanoseconds of each event during which it is the innermost one
    running (the one that started last), in the order of ``events``: a
    ``while``'s time less its body's instructions'. Every instant some
    event covers goes to exactly one, so the sum is the length of the
    union, however the events overlap."""
    own = [0.0] * len(events)
    stack, at = [], float("-inf")

    def advance(to):
        nonlocal at
        while stack and at < to:
            top = events[stack[-1]]
            if top.end <= at:
                stack.pop()
                continue
            upto = min(top.end, to)
            own[stack[-1]] += upto - at
            at = upto
        at = max(at, to)

    for i in sorted(range(len(events)),
                    key=lambda i: (events[i].start, -events[i].end)):
        advance(events[i].start)
        stack.append(i)
    advance(float("inf"))
    return own


def _log(msg):
    print("[bench] " + msg, file=sys.stderr, flush=True)


def _scope_label(scope):
    """The scope an instruction ran under, without the wrappers, the
    primitive and the layer's number: what the largest-scopes line sums
    by."""
    path = _path(scope)
    label = "/".join(path[:-1] if len(path) > 1 else path)
    return re.sub(r"_\d+(?=\b|_)", "_*", label) or "(no scope)"


def build(win, hlo_text, n_steps, log=_log):
    """The table of one chip's window, or None where the program has no
    scopes to join or the self-times do not add up."""
    try:
        from horovod_tpu.jax import introspect

        scopes = introspect.instruction_scopes(hlo_text)
    except (ImportError, AttributeError):
        log("scope view: this program has no instruction_scopes; no "
            "per-scope metric")
        return None
    by_cell = defaultdict(float)      # (phase, part) -> ns
    by_scope = defaultdict(float)
    remaining = defaultdict(float)    # unscoped instruction -> ns
    for event, own in zip(win.ops, self_times(win.ops)):
        name = tr.instruction_name(event.name)
        scope = scopes.get(name, "")
        cell = classify(scope, event.name)
        by_cell[cell] += own
        by_scope[_scope_label(scope)] += own
        if cell[0] == "unscoped":
            remaining[name] += own
    busy = tr.length(tr.spans(win.ops))
    total = sum(by_cell.values())
    per_step = 1e-9 / max(n_steps, 1)
    table = SimpleNamespace(
        cells={k: v * per_step for k, v in by_cell.items()},
        busy_s=busy * per_step, self_s=total * per_step,
        async_s=dict(tr.time_by(
            win.async_ops,
            lambda text: _scope_label(
                scopes.get(tr.instruction_name(text), "")))),
        unscoped=sorted(remaining.items(), key=lambda kv: -kv[1])[:10])
    table.phase_s = {p: sum(v for (ph, _), v in table.cells.items()
                            if ph == p) for p in PHASES}
    parts = sorted({part for _, part in table.cells})
    table.part_s = {p: sum(v for (_, part), v in table.cells.items()
                           if part == p) for p in parts}
    log("scope view, self-time per step (ms): " + "; ".join(
        "%s %.3f (%s)" % (phase, 1e3 * table.phase_s[phase], ", ".join(
            "%s %.3f" % (part, 1e3 * v)
            for (ph, part), v in sorted(table.cells.items()) if ph == phase))
        for phase in PHASES if table.phase_s[phase]))
    top = sorted(by_scope.items(), key=lambda kv: -kv[1])[:10]
    log("largest scopes, self-time per step (ms): " + ", ".join(
        "%s %.3f" % (k, 1e3 * v * per_step) for k, v in top))
    if table.async_s:
        log("asynchronous pairs, start to done, not in the self-times, per "
            "step (ms): " + ", ".join(
                "%s %.3f" % (k, 1e3 * v / max(n_steps, 1))
                for k, v in list(table.async_s.items())[:5]))
    if table.unscoped:
        log("unscoped instructions, self-time per step (ms): " + ", ".join(
            "%s %.4f" % (k, 1e3 * v * per_step) for k, v in table.unscoped))
    if not busy or abs(total - busy) > TOLERANCE * busy:
        log("scope view: self-times add to %.6f s, the busy union is %.6f "
            "s; no per-scope metric from this trace"
            % (total * 1e-9, busy * 1e-9))
        return None
    return table


def table(ctx):
    """``build`` for chip 0's window of ``ctx``, made once. Never raises:
    a reader can lose its metric, the run keeps its line."""
    if not hasattr(ctx, "_scope_table"):
        try:
            ctx._scope_table = build(ctx.win0, ctx.hlo_text, ctx.n_steps)
        except Exception as e:   # noqa: BLE001 - a reader never raises
            _log("scope view failed: %s: %s" % (type(e).__name__, e))
            ctx._scope_table = None
    return ctx._scope_table


# ------------------------------------------------------------ readers -----

def phase_ms(ctx, phase):
    t = table(ctx)
    return None if t is None else 1e3 * t.phase_s[phase]


def part_ms(ctx, part):
    t = table(ctx)
    if t is None or part not in t.part_s:
        return None
    return 1e3 * t.part_s[part]


def unscoped_pct(ctx):
    t = table(ctx)
    return None if t is None else 100.0 * t.phase_s["unscoped"] / t.busy_s


def kernel_roofline(ctx, directions=("fwd", "bwd")):
    """The flash kernels against the work attention REQUIRES: the least
    time the chip could take for the step's ``fwd`` and / or ``bwd``
    work (``ctx.attention``: the builder's sum of
    ``flops.attention_work`` over its layers) over the time of the calls
    of those directions, found by name: ``hvd_flash_fwd`` is the
    forward, every other ``hvd_flash_*`` the backward
    (``trace_reduce.direction``). A backward of one kernel and one of
    two read the same work; the calls are not counted. None where the
    trace holds no such call or the builder states no attention."""
    from benchmark import flops

    try:
        work = [ctx.attention[d] for d in directions]
        took = sum(s for kernel, (s, _)
                   in tr.kernel_seconds(ctx.win0.ops).items()
                   if tr.direction(kernel) in directions)
    except (AttributeError, KeyError, TypeError):
        return None
    if not took:
        return None
    least = sum(flops.roofline_seconds(ops, nbytes, ctx.peak)[0]
                for ops, nbytes in work)
    return 100.0 * least * ctx.n_steps / took
