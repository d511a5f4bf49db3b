"""From a profiler trace (``.xplane.pb``) to numbers.

What a TPU v5e trace holds (jax 0.9.0 / libtpu 0.0.34, looked at by
hand, PERF.md section 3): one plane ``/device:TPU:<n>`` per chip with
the lines ``XLA Modules`` (one event per executed program, named
``<hlo module>(<fingerprint>)``), ``XLA Ops`` (one event per executed
instruction; the event's NAME is the instruction's whole HLO text,
``%name = type opcode(operands), attributes``; there is no category
stat) and ``Async XLA Ops`` (one event per asynchronous pair, named by
its ``-start`` instruction, lasting from the start to the done). Host
threads are lines of the plane ``/host:CPU``; ``TraceAnnotation`` spans
land on the line of the thread that made them. Times are nanoseconds;
the device's and the host's clocks differ by up to a millisecond.

Everything below the loader works on plain ``Event`` tuples, so the
arithmetic is tested on hand-built events (``benchmark/tests``).
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
_INSTRUCTION = re.compile(r"^%(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9_-]*)\(")
# The names ops/pallas_attention.py gives its Mosaic calls: a prefix and
# the kernel; the forward's is the one kernel name with a meaning here.
FLASH, FORWARD = "hvd_flash_", "fwd"

Interval = Tuple[float, float]


class Event(NamedTuple):
    name: str
    start: float   # ns
    end: float     # ns

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


class Trace(NamedTuple):
    devices: Dict[int, Dict[str, List[Event]]]   # chip -> line -> events
    host: Dict[str, List[Event]]                 # thread line -> events


def load(path) -> Trace:
    """Read an ``.xplane.pb`` with nothing but jax."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in data.planes:
        chip = DEVICE_PLANE.match(plane.name)
        if not chip and plane.name != HOST_PLANE:
            continue
        lines = {}
        for line in plane.lines:
            events = [Event(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events]
            if events:
                events.sort(key=lambda e: e.start)
                lines[line.name] = events
        if chip:
            devices[int(chip.group(1))] = lines
        else:
            host = lines
    return Trace(devices, host)


# ---------------------------------------------------------- intervals -----

def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint."""
    merged: List[Interval] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def length(intervals: Sequence[Interval]) -> float:
    return sum(hi - lo for lo, hi in union(intervals))


def clip(intervals: Sequence[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps(intervals: Sequence[Interval], lo: float, hi: float):
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for a, b in union(clip(intervals, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def uncovered(intervals: Sequence[Interval], others: Sequence[Interval]):
    """Length of the part of ``intervals`` (as a union) during which
    none of ``others`` runs."""
    others = union(others)
    return sum(length(gaps(others, lo, hi)) for lo, hi in union(intervals))


def spans(events: Sequence[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


# ------------------------------------------------------- instructions -----

def instruction_name(event_name: str) -> str:
    """``%fusion.6 = bf16[...] fusion(...)`` -> ``fusion.6``."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


def opcode(event_name: str) -> str:
    """The HLO opcode of an ``XLA Ops`` event, '' if it has none."""
    m = _INSTRUCTION.match(event_name)
    if not m:
        return ""
    m = _OPCODE.search(event_name, m.end() - 1)
    return m.group(1) if m else ""


def is_mosaic_call(event_name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in event_name


def named_kernel(event_name: str, prefix: str) -> str:
    """What follows ``prefix`` in the NAME of a Mosaic call, '' for any
    other instruction and any other name. ``pallas_call(name=)`` names
    the instruction (``%hvd_flash_dkv.47 = ...`` is ``dkv`` under
    ``hvd_flash_``); a call's operands and results say nothing here, so
    a kernel takes the operands it needs."""
    if not is_mosaic_call(event_name):
        return ""
    name = instruction_name(event_name)
    if not name.startswith(prefix):
        return ""
    return name[len(prefix):].split(".")[0]


def flash_kernel(event_name: str) -> str:
    """Which kernel of ops/pallas_attention.py a Mosaic call is: what
    follows ``hvd_flash_`` in its name (``fwd``, ``dkv`` and ``dq``
    today; whatever a later kernel is called), '' for every other
    call."""
    return named_kernel(event_name, FLASH)


def direction(kernel: str) -> str:
    """``fwd`` or ``bwd``, the direction of ``flops.attention_work``
    that a kernel named ``hvd_flash_<kernel>`` (or ``hvd_dsa_<kernel>``)
    works for: every name but the forward's belongs to the backward
    pass, so that a backward of one kernel, of two or of three is read
    against the same required work."""
    return "fwd" if kernel == FORWARD else "bwd"


def kernel_seconds(events: Sequence["Event"], prefix: str = FLASH):
    """{kernel: (seconds, calls)} of the Mosaic calls among ``events``
    whose name starts with ``prefix``, by what follows it."""
    took: Dict[str, Tuple[float, int]] = {}
    for e in events:
        kernel = named_kernel(e.name, prefix)
        if kernel:
            seconds, calls = took.get(kernel, (0.0, 0))
            took[kernel] = (seconds + e.seconds, calls + 1)
    return took


def is_collective(event_name: str) -> bool:
    return opcode(event_name).startswith(
        ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
         "collective-permute"))


def category(event_name: str) -> str:
    """A coarse class for the where-the-time-goes table."""
    op = opcode(event_name)
    name = instruction_name(event_name)
    if is_mosaic_call(event_name):
        return "flash " + (flash_kernel(event_name) or "other")
    if is_collective(event_name):
        return "collective"
    if op == "fusion" and "convolution" in name:
        return "matmul/conv fusion"
    if op in ("convolution", "dot"):
        return "matmul/conv"
    if op == "fusion":
        return "other fusion"
    if op.startswith("copy") or op in ("transpose", "bitcast", "reshape"):
        return "copy/layout"
    return op or "other"


# ------------------------------------------------------------ device ------

class DeviceWindow(NamedTuple):
    """One chip's part of the steady window: from the start of the first
    run of the step program in the trace to the end of the last."""
    lo: float
    hi: float
    steps: List[Event]       # the step program's runs
    ops: List[Event]         # XLA Ops inside the window
    async_ops: List[Event]   # Async XLA Ops inside the window

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    @property
    def busy_s(self) -> float:
        return length(spans(self.ops)) * 1e-9


def device_window(lines: Dict[str, List[Event]], module: str) -> DeviceWindow:
    steps = [e for e in lines.get("XLA Modules", [])
             if e.name.split("(")[0] == module]
    if not steps:
        raise ValueError("no run of module %r in the trace (modules: %s)" % (
            module, sorted({e.name for e in lines.get("XLA Modules", [])})))
    lo, hi = steps[0].start, steps[-1].end

    def inside(events):
        return [e for e in events if e.start >= lo and e.end <= hi]

    return DeviceWindow(lo, hi, steps, inside(lines.get("XLA Ops", [])),
                        inside(lines.get("Async XLA Ops", [])))


def time_by(events: Sequence[Event], key) -> Dict[str, float]:
    """Seconds summed by ``key(event.name)``, largest first."""
    total: Dict[str, float] = defaultdict(float)
    for e in events:
        total[key(e.name)] += e.seconds
    return dict(sorted(total.items(), key=lambda kv: -kv[1]))


def collective_intervals(win: DeviceWindow) -> List[Interval]:
    """When a collective is under way on this chip: synchronous
    collective instructions for as long as they run, asynchronous ones
    from their start to their done."""
    return spans([e for e in win.ops + win.async_ops
                  if is_collective(e.name)])


def exposed_collective(win: DeviceWindow) -> float:
    """Nanoseconds of ``collective_intervals`` during which no other
    instruction runs on the chip."""
    others = spans([e for e in win.ops if not is_collective(e.name)])
    return uncovered(collective_intervals(win), others)


# -------------------------------------------------------------- host ------

def host_offset(win: DeviceWindow, dispatch: Sequence[Event]) -> float:
    """What to add to a host time to put it on the device's clock,
    estimated from the first traced step: the device was drained before
    it, so its program starts as its dispatch span ends, give or take
    the launch (tens of microseconds)."""
    return win.steps[0].start - dispatch[0].end if dispatch else 0.0


def attribute_gaps(win: DeviceWindow, host_spans: Sequence[Event],
                   offset: float, top: int = 5):
    """The ``top`` longest idle gaps of the window, each named by the
    host span (shifted by ``offset``) that overlaps it most:
    [[name, seconds], ...]."""
    idle = sorted(gaps(spans(win.ops), win.lo, win.hi),
                  key=lambda g: g[0] - g[1])[:top]
    named = []
    for lo, hi in idle:
        best, best_overlap = "no_host_span", 0.0
        for e in host_spans:
            overlap = min(hi, e.end + offset) - max(lo, e.start + offset)
            if overlap > best_overlap:
                best, best_overlap = e.name, overlap
        named.append([best, (hi - lo) * 1e-9])
    return named


def annotations(trace: Trace, names: Sequence[str]) -> List[Event]:
    """The harness's own ``TraceAnnotation`` spans, from whichever host
    thread made them."""
    found = [e for events in trace.host.values() for e in events
             if e.name in names]
    return sorted(found, key=lambda e: e.start)
