"""Chrome-tracing timeline for collective operations.

Analog of the reference's Horovod Timeline
(reference: horovod/common/timeline.cc:496-678 — per-tensor negotiation
and operation phases written as chrome://tracing JSON, toggled by
``HOROVOD_TIMELINE`` or hvd.start_timeline). The eager layer records a
span per submitted tensor from enqueue to completion; like the
reference, the file is a JSON event array left open for streaming
(chrome://tracing accepts an unterminated array).

The launch has a span log of its own (``SpanLog``, ``LAUNCH_LOG``):
what happens between ``import horovod_tpu`` and the first step --
``hvd.init()`` and its parts, ``hvd.plan()``, ``Plan.apply()``, and
every program jax traces, lowers and compiles or reads from its cache
(``utils/compile_cache.py`` listens to jax's own events) -- is kept in
memory on ``time.time()`` and read with ``hvd.launch_spans()``; an open
``Timeline`` receives each span as a ``B``/``E`` pair under category
``launch`` (docs/timeline.md#launch). What jax was tracing inside a
``compile/trace`` span is in the ``trace/*`` spans the traced modules
file through ``trace_span``.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import threading
import time
from typing import Dict, List, Optional

from horovod_tpu.utils import metrics as _metrics


class Timeline:
    def __init__(self, file_path: str, mark_cycles: bool = False):
        # mark_cycles is accepted for API symmetry but acted on by the
        # NATIVE writer (the op-level writer has no background cycle to
        # mark); basics.start_timeline plumbs it through to the core.
        del mark_cycles
        self._lock = threading.Lock()
        self._f = open(file_path, "w")
        self._f.write("[\n")
        self._t0 = time.perf_counter()
        # The same instant on the wall clock: a launch span keeps
        # ``time.time()`` and is placed by it (``span``).
        self._t0_wall = time.time()
        self._closed = False
        self._buf = []
        self._stop_flusher = threading.Event()
        # Background flusher (reference: timeline.cc TimelineWriter
        # thread): drains the buffer on a period INDEPENDENT of producer
        # activity, so when the job wedges mid-collective the stuck
        # op's begin event still reaches disk.
        self._flusher = threading.Thread(
            target=self._flush_loop, daemon=True, name="hvd-timeline")
        self._flusher.start()
        from horovod_tpu.common import basics

        self._pid = basics.rank() if basics.is_initialized() else 0
        self._write({"name": "process_name", "ph": "M", "pid": self._pid,
                     "args": {"name": "horovod_tpu rank %d" % self._pid}})

    def _now_us(self) -> float:
        return (time.perf_counter() - self._t0) * 1e6

    # Producers only append under the lock; disk IO happens on the
    # flusher thread (every _FLUSH_SECONDS) or inline past _FLUSH_EVERY
    # pending events (backpressure bound).
    _FLUSH_EVERY = 64
    _FLUSH_SECONDS = 1.0

    def _flush_locked(self):
        # analysis: holds-lock(_lock) — the _locked suffix is the
        # contract: every caller takes self._lock before calling.
        if self._buf:
            self._f.write("".join(self._buf))
            self._buf.clear()
            self._f.flush()

    def _flush_loop(self):
        while not self._stop_flusher.wait(self._FLUSH_SECONDS):
            with self._lock:
                if self._closed:
                    return
                self._flush_locked()

    def _write(self, event: dict):
        line = json.dumps(event) + ",\n"
        with self._lock:
            if self._closed:
                return
            self._buf.append(line)
            if len(self._buf) >= self._FLUSH_EVERY:
                self._flush_locked()

    # ``pid`` overrides the event's process row: the merged multi-rank
    # trace writer (tools/trace) reuses this class with one row per
    # rank; in-process callers leave it None (this rank's row).

    def begin(self, name: str, category: str,
              args: Optional[dict] = None, pid: Optional[int] = None):
        ev = {"name": name, "cat": category, "ph": "B",
              "ts": self._now_us(),
              "pid": self._pid if pid is None else pid, "tid": category}
        if args:
            ev["args"] = args
        self._write(ev)

    def end(self, name: str, category: str, args: Optional[dict] = None,
            pid: Optional[int] = None):
        ev = {"name": name, "cat": category, "ph": "E",
              "ts": self._now_us(),
              "pid": self._pid if pid is None else pid, "tid": category}
        if args:
            ev["args"] = args
        self._write(ev)

    def instant(self, name: str, pid: Optional[int] = None):
        self._write({"name": name, "ph": "i", "ts": self._now_us(),
                     "pid": self._pid if pid is None else pid, "s": "p"})

    def span(self, name: str, category: str, start: float, end: float,
             args: Optional[dict] = None, tid: Optional[str] = None):
        """A FINISHED span as a ``B``/``E`` pair, placed by its
        ``time.time()`` edges (before this file was opened: a negative
        ``ts``, which the viewers accept)."""
        for ph, wall in (("B", start), ("E", end)):
            ev = {"name": name, "cat": category, "ph": ph,
                  "ts": (wall - self._t0_wall) * 1e6, "pid": self._pid,
                  "tid": category if tid is None else tid}
            if args and ph == "B":
                ev["args"] = args
            self._write(ev)

    def write_raw(self, event: dict):
        """Append one pre-built Chrome-trace event (tools/trace's
        merged-trace path: events carry their own ts/pid/tid)."""
        self._write(event)

    def record_future(self, name: str, category: str, future,
                      seq: Optional[int] = None):
        """Span from now until the future resolves. ``seq`` is the
        per-process-set collective sequence number (ops/eager.py
        _next_seq), stamped on both edges so cross-rank tooling can
        align this op with its flight-recorder events."""
        self.begin(name, category,
                   args=None if seq is None else {"seq": seq})

        def _done(f):
            err = f.exception()
            args = {"status": "error" if err else "ok"}
            if seq is not None:
                args["seq"] = seq
            self.end(name, category, args=args)

        future.add_done_callback(_done)

    def close(self):
        self._stop_flusher.set()
        with self._lock:
            if not self._closed:
                self._closed = True
                self._flush_locked()
                self._f.close()


# --- the launch's span log ---------------------------------------------------

LAUNCH_CATEGORY = "launch"
# The program's own phases of a launch, span name -> label of
# hvd_launch_phase_seconds.
LAUNCH_PHASES = {"import": "import", "init": "init", "plan": "plan",
                 "plan/apply": "apply"}

_G_LAUNCH_PHASE = _metrics.gauge(
    "hvd_launch_phase_seconds",
    "Seconds the newest launch spent in the program's own phases: "
    "import (horovod_tpu's import, once a process), init (hvd.init()), "
    "plan (every hvd.plan() call since that init), apply "
    "(Plan.apply()). The spans behind them: hvd.launch_spans().",
    ("phase",))


class SpanLog:
    """Spans of a process's launches, in memory: the newest
    ``capacity`` of them, each a dict of ``id``, ``parent`` (the span
    open on the same thread when this one began, else None), ``launch``
    (the count of ``hvd.init()`` calls begun in this process, at least
    1), ``name``, ``start`` and ``end`` on ``time.time()`` (``end`` None
    while the span is open) and ``args``. ``dropped`` counts the spans
    that fell off the old end: a sum over a log that dropped any is
    partial.

    Written to from the few places a launch passes through, from a
    traced function only through ``trace_span`` (which files nothing
    outside a compile phase) and never from a training loop; an
    attached ``Timeline`` receives every span that closes (and, when
    attached, those that closed before)."""

    def __init__(self, capacity: int = 4096, phase_gauge=None):
        self._lock = threading.Lock()
        self._spans = collections.deque(maxlen=capacity)
        self.dropped = 0
        self._ids = itertools.count(1)
        self._launches = 0
        self._open = threading.local()
        self._sink: Optional[Timeline] = None
        self._phase_gauge = phase_gauge

    def begin_launch(self) -> int:
        """Called by ``hvd.init()`` as it begins: spans from here on
        belong to the next launch. Returns its number."""
        with self._lock:
            self._launches += 1
            launch = self._launches
        if self._phase_gauge is not None:
            for phase in LAUNCH_PHASES.values():
                if phase != "import":   # once a process, not a launch
                    self._phase_gauge.labels(phase=phase).set(0.0)
        return launch

    def _stack(self) -> List[dict]:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _new(self, name, start, args) -> dict:
        stack = self._stack()
        # The innermost span open on this thread when this one began
        # (a span filed after the fact may have begun before it).
        parent = next((s["id"] for s in reversed(stack)
                       if s["start"] <= start), None)
        with self._lock:
            span = {"id": next(self._ids), "parent": parent,
                    "launch": max(self._launches, 1), "name": name,
                    "start": start, "end": None, "args": args}
            self.dropped += len(self._spans) == self._spans.maxlen
            self._spans.append(span)
        return span

    def _close(self, span: dict, end: float):
        with self._lock:   # against attach(): emitted once, by one side
            span["end"] = end
            sink = self._sink
        if self._phase_gauge is not None and span["name"] in LAUNCH_PHASES:
            self._phase_gauge.labels(
                phase=LAUNCH_PHASES[span["name"]]).inc(end - span["start"])
        if sink is not None:
            self._emit(sink, span)

    @staticmethod
    def _emit(sink: "Timeline", span: dict):
        args = dict(span["args"], id=span["id"], launch=span["launch"])
        if span["parent"] is not None:
            args["parent"] = span["parent"]
        sink.span(span["name"], LAUNCH_CATEGORY, span["start"],
                  span["end"], args=args)

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Open a span round the ``with`` body; yields its ``args``
        dict, which the body may add to."""
        span = self._new(name, time.time(), args)
        stack = self._stack()
        stack.append(span)
        try:
            yield args
        finally:
            stack.remove(span)
            self._close(span, time.time())

    def record(self, name: str, start: float, end: float, **args):
        """File a span that has already ended (jax reports a compile
        phase when it is over)."""
        self._close(self._new(name, start, args), end)

    def spans(self) -> List[Dict]:
        """Copies of the spans kept, oldest first."""
        with self._lock:
            return [dict(s, args=dict(s["args"])) for s in self._spans]

    def attach(self, timeline: Optional["Timeline"]):
        """Send every span that closes from now on to ``timeline`` too,
        and first those kept that closed before it was opened (the
        import, the ``init`` under way); None detaches."""
        with self._lock:
            self._sink = timeline
            closed = [s for s in self._spans if s["end"] is not None]
        if timeline is not None:
            for span in closed:
                self._emit(timeline, span)


LAUNCH_LOG = SpanLog(phase_gauge=_G_LAUNCH_PHASE)


def launch_spans() -> List[Dict]:
    """``hvd.launch_spans()``: what this process's launches were made
    of (docs/timeline.md#launch)."""
    return LAUNCH_LOG.spans()


def trace_span(part: str, **args):
    """A ``trace/<part>`` span of the launch log round the ``with``
    body, ONLY while jax is tracing on this thread (a compile phase is
    open: ``utils/compile_cache.py`` ``CompileListener.tracing``;
    ``jax.eval_shape`` is one too). The traced modules call it where
    the trace's seconds go -- a block, a kernel body, an expert layer,
    the gradient sync, the update -- so that a ``compile/trace`` span
    divides by what was traced inside it (docs/timeline.md#launch).
    Anywhere else (an eager call, a training loop) it costs one
    thread-local read and files nothing. The ``with`` target is the
    span's ``args``, or None where there is none."""
    from horovod_tpu.utils import compile_cache   # imports this module

    if not compile_cache.tracing():
        return contextlib.nullcontext()
    return LAUNCH_LOG.span("trace/" + part, **args)
