"""From a profiler trace (``.xplane.pb``) to what the metrics read.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per
executed program, named ``jit_<function>(<fingerprint>)``), ``XLA Ops``
(one event per HLO instruction, named by its HLO text
``%fusion.12 = bf16[...] fusion(...)``; a ``while`` holds its body's
events inside its own interval) and ``Async XLA Ops``; and the plane
``/host:CPU`` whose lines are host threads, ``jax.profiler
.TraceAnnotation`` spans among them.  Device and host stamps share an
axis to about a millisecond (the first device event of a program can
read ~1 ms before the host call that launched it), so a gap is
attributed to a host span, not measured against it.

Everything below works on plain tuples, so the tests can feed it a
recorded trace or a synthetic one.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

#: (name, start_ns, duration_ns)
Event = Tuple[str, float, float]

_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"
_ASYNC_LINE = "Async XLA Ops"
WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"
_HLO_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)? = ")
_MODULE_NAME = re.compile(r"^(.*?)\(\d+\)$")
_CALLS = re.compile(r"calls=%?([A-Za-z][\w\-]*)")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "all-to-all", "collective-permute", "collective-broadcast")


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: List[Event]
    modules: List[Event]
    #: ``Async XLA Ops``: start-to-done intervals of asynchronous
    #: copies and collectives, which overlap the ops line
    async_ops: List[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    devices: List[DeviceTrace]
    host_spans: List[Event]
    #: the traced window on the trace's own axis
    t0_ns: float
    t1_ns: float

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def load(path: str, span_prefix: str = "bench.") -> Trace:
    """Read an ``.xplane.pb`` with nothing but JAX.  Host spans are the
    host-plane events whose name starts with `span_prefix`.  The window
    runs between the spans ``bench.window_start`` and
    ``bench.window_end`` where the driver wrote them, and else from the
    first to the last device event (a driver that traces whole steps);
    device events are clipped to it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[DeviceTrace] = []
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines
                     if line.name in (_OPS_LINE, _MODULES_LINE,
                                      _ASYNC_LINE)}
            devices.append(DeviceTrace(
                plane.name, lines.get(_OPS_LINE, []),
                lines.get(_MODULES_LINE, []),
                lines.get(_ASYNC_LINE, [])))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(span_prefix))
    devices.sort(key=lambda d: d.name)
    spans.sort(key=lambda e: e[1])
    return windowed(devices, spans, path)


def windowed(devices: List[DeviceTrace], spans: List[Event],
             origin: str = "trace") -> Trace:
    stamps = [(s, s + d) for dev in devices
              for _, s, d in (dev.modules or dev.ops)]
    if not stamps:
        raise ValueError(f"{origin}: no operation ran on a device")
    t0 = min(s for s, _ in stamps)
    t1 = max(e for _, e in stamps)
    marks = {name: s for name, s, _ in spans
             if name in (WINDOW_START, WINDOW_END)}
    if len(marks) == 2:
        t0, t1 = marks[WINDOW_START], marks[WINDOW_END]
        devices = [DeviceTrace(d.name, _clip(d.ops, t0, t1),
                               _clip(d.modules, t0, t1),
                               _clip(d.async_ops, t0, t1))
                   for d in devices]
    return Trace(devices, spans, t0, t1)


def _clip(events: Sequence[Event], t0: float, t1: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a or (d == 0 and t0 <= s <= t1):
            out.append((name, a, b - a))
    return out


def op_kind(hlo_text: str) -> str:
    """``%add_add_fusion.2 = bf16[...] fusion(...)`` -> ``add_add_fusion``."""
    m = _HLO_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text.split(" ")[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_step(887015...)`` -> ``jit_step``."""
    m = _MODULE_NAME.match(event_name)
    return m.group(1) if m else event_name


def is_mosaic(hlo_text: str) -> bool:
    return MOSAIC_TARGET in hlo_text or hlo_text.startswith(
        "%tpu_custom_call")


def is_collective(hlo_text: str) -> bool:
    """A collective instruction, its ``-start``/``-done`` halves, or a
    fusion the compiler built around one (``fusion(...), kind=kCustom,
    calls=all-reduce-scatter.1``)."""
    kind = op_kind(hlo_text)
    if any(kind.startswith(c) for c in COLLECTIVES):
        return True
    m = _CALLS.search(hlo_text)
    return bool(m) and any(m.group(1).startswith(c) for c in COLLECTIVES)


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """The parts of disjoint sorted `a` that no interval of disjoint
    sorted `b` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _spans(events: Iterable[Event]) -> List[Tuple[float, float]]:
    return [(s, s + d) for _, s, d in events]


def busy_intervals(dev: DeviceTrace) -> List[Tuple[float, float]]:
    """Where an operation ran on the device.  Ops, not modules: a
    module's interval covers stalls inside the program too."""
    return union(_spans(dev.ops or dev.modules))


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    return sum(total(busy_intervals(d)) for d in trace.devices) \
        / len(trace.devices) / 1e9


def self_times(ops: Sequence[Event]) -> List[Tuple[str, float]]:
    """(hlo text, self nanoseconds) per event: its duration less what
    the events nested inside it (a ``while``'s body) cover."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    self_ns = [ops[i][2] for i in range(len(ops))]
    stack: List[int] = []
    for i in order:
        _, s, d = ops[i]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(ops[i][0], max(self_ns[i], 0.0)) for i in range(len(ops))]


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The n kinds of device operation with most self time, in seconds
    averaged over the devices."""
    acc: Dict[str, float] = {}
    for dev in trace.devices:
        for text, ns in self_times(dev.ops):
            kind = op_kind(text)
            if is_mosaic(text):
                kind = "mosaic:" + _signature(text)
            acc[kind] = acc.get(kind, 0.0) + ns
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9 / len(trace.devices)] for k, v in ranked]


def _signature(hlo_text: str) -> str:
    """A Mosaic call has no name of its own in the trace (its
    kernel_metadata is empty), so it is told apart by its result
    shapes: ``bf16_288_1024_64+f32_288_1_1024``."""
    result = hlo_text.split(" = ", 1)[-1].split(" custom-call(")[0]
    shapes = re.findall(r"([a-z]+\d+)\[([\d,]*)\]", result)
    return "+".join(f"{t}_{dims.replace(',', '_')}" for t, dims in shapes)


def events_matching(dev: DeviceTrace, pred) -> List[Event]:
    return [e for e in dev.ops if pred(e[0])]


def module_events(trace: Trace, name: str) -> List[List[Event]]:
    """Per device, the executions of the program `name` (``jit_step``)."""
    return [[e for e in dev.modules if module_name(e[0]) == name]
            for dev in trace.devices]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The idle time of the first device, grouped by what surrounds it:
    ``<module before>-<module after> host:<span>``, seconds, the n
    largest groups.  The host span is the benchmark's own annotation
    that covers most of the gap."""
    dev = trace.devices[0]
    busy = busy_intervals(dev)
    gaps = subtract([(trace.t0_ns, trace.t1_ns)], busy)
    mods = sorted(dev.modules, key=lambda e: e[1])
    acc: Dict[str, float] = {}
    for s, e in gaps:
        before = [m for m in mods if m[1] <= s]
        after = [m for m in mods if m[1] >= e]
        inside = [m for m in mods if m[1] < s and m[1] + m[2] > e]
        if inside:
            where = "inside " + module_name(inside[-1][0])
        else:
            where = (f"{module_name(before[-1][0]) if before else 'start'}"
                     f" - {module_name(after[0][0]) if after else 'end'}")
        best, cover = "none", 0.0
        for name, hs, hd in trace.host_spans:
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        key = f"{where} host:{best}"
        acc[key] = acc.get(key, 0.0) + (e - s)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / 1e9] for k, v in ranked]
