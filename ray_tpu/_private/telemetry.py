"""Shared telemetry primitives for the serve/train hot paths.

The engine telemetry layer (serve/telemetry.py, train/telemetry.py)
works on HOST-side timestamps only — nothing here ever touches a
device buffer or forces a sync; producers time around syncs the hot
path already performs (the np.asarray fence in the decode engine, the
float(loss) fence in training loops).

Three shared pieces live here:

* percentile summaries over raw latency samples (the ``engine_stats()``
  p50/p95/p99 blocks), nearest-rank so a 3-sample TTFT series reports
  its actual observations, not interpolated fiction;
* chrome-trace event builders emitting the exact shape
  ``ray_tpu.timeline()`` writes (name/cat/ph/ts/dur/pid/tid/args, ts in
  microseconds) so engine timelines and task timelines open in the same
  chrome://tracing / Perfetto view;
* host phases (:class:`Phases`): what a loop does between device
  calls, as ``raytpu.<layer>.<phase>`` spans on the
  profiler's own clock plus one ``{phase: [count, seconds]}`` table;
* set-up records (`record_setup`, `setup_records`): one small host
  record for every program JAX compiled or loaded, every side compile
  of ``device_stats.instrument`` and every phase of an engine's build,
  each with the span that caused it (`cause`), in one ring a process.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import math
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterator, List, Optional, Sequence

from ray_tpu._private import scopes

#: percentiles every summarize() block reports
PERCENTILES = (50, 95, 99)


def percentile(sorted_values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over an ascending-sorted sample."""
    if not sorted_values:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(sorted_values)))
    return float(sorted_values[rank - 1])


def summarize(values: Sequence[float]) -> Dict[str, Any]:
    """{count, mean, p50, p95, p99, max} over raw samples (all None
    except count=0 when empty, so JSON consumers see a stable shape)."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return {"count": 0, "mean": None, "p50": None, "p95": None,
                "p99": None, "max": None}
    out: Dict[str, Any] = {
        "count": len(vals),
        "mean": round(sum(vals) / len(vals), 3),
        "max": round(vals[-1], 3),
    }
    for q in PERCENTILES:
        out[f"p{q}"] = round(percentile(vals, q), 3)
    return out


# ---------------------------------------------------------------------------
# chrome-trace builders (same event shape as ray_tpu.timeline())
# ---------------------------------------------------------------------------

def complete_event(name: str, cat: str, ts_s: float, dur_s: float,
                   pid: int, tid: int,
                   args: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    """A chrome-trace "X" (complete) event; ts/dur seconds → µs."""
    return {"name": name, "cat": cat, "ph": "X",
            "ts": ts_s * 1e6, "dur": max(0.0, dur_s) * 1e6,
            "pid": pid, "tid": tid, "args": args or {}}


def instant_event(name: str, cat: str, ts_s: float, pid: int, tid: int,
                  args: Optional[Dict[str, Any]] = None
                  ) -> Dict[str, Any]:
    """A chrome-trace "i" (instant) event."""
    return {"name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": ts_s * 1e6, "pid": pid, "tid": tid, "args": args or {}}


def process_name_event(pid: int, name: str) -> Dict[str, Any]:
    return {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
            "args": {"name": name}}


def thread_name_event(pid: int, tid: int, name: str) -> Dict[str, Any]:
    return {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": name}}


def write_chrome_trace(events: List[Dict[str, Any]],
                       filename: Optional[str]) -> List[Dict[str, Any]]:
    """Dump events as chrome-trace JSON (a bare event array, the format
    ray_tpu.timeline() writes); returns the events for chaining."""
    if filename:
        with open(filename, "w") as f:
            json.dump(events, f)
    return events


# ---------------------------------------------------------------------------
# host phases on the profiler's clock
# ---------------------------------------------------------------------------

def _annotation(name: str, attrs: Optional[Dict[str, Any]] = None):
    """A ``jax.profiler.TraceAnnotation`` ``raytpu.<name>``, entered,
    or None in a process that has not imported JAX (the runtime's
    driver never does).  `attrs` become the event's stats (the
    profiler's host tracer reads them off the name it is handed,
    ``name#k=v,...#``, and keeps the bare name); they are encoded only
    while a session is active.  Without a profiler session the
    annotation records nothing."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    ann = jax.profiler.TraceAnnotation(scopes.SPAN_PREFIX + name,
                                       **(attrs or {}))
    ann.__enter__()
    return ann


class _Phase:
    """One ``with phases.phase(name, **attrs)`` block; ``t0`` and ``t1``
    are its ``perf_counter`` stamps, for a caller that wants the
    duration it would otherwise time a second time."""

    __slots__ = ("_owner", "_name", "_attrs", "t0", "t1")

    def __init__(self, owner: "Phases", name: str,
                 attrs: Optional[Dict[str, Any]] = None):
        self._owner, self._name, self._attrs = owner, name, attrs
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_Phase":
        self.t0 = self._owner._push(self._name, self._attrs) * 1e-9
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = self._owner._pop() * 1e-9
        return False


class _Step:
    """One ``with phases.step(**attrs)`` block; ``t0`` is the
    ``perf_counter`` stamp its first leaf starts on."""

    __slots__ = ("_owner", "_attrs", "t0")

    def __init__(self, owner: "Phases",
                 attrs: Optional[Dict[str, Any]] = None):
        self._owner, self._attrs = owner, attrs
        self.t0 = 0.0

    def __enter__(self) -> "_Step":
        self.t0 = self._owner._begin_step(self._attrs) * 1e-9
        return self

    def __exit__(self, *exc) -> bool:
        self._owner._end_step()
        return False


class Phases:
    """The host phases of one loop (one engine, one trainer).

    ``with phases.phase("admit"):`` opens the span
    ``raytpu.<layer>.admit`` and books its time under ``admit``.  Phases
    nest, and only the innermost one runs: entering a child closes the
    parent's span and books the parent's time so far, leaving the child
    re-opens the parent.  So the spans are leaves -- they never overlap
    -- and one stamp both ends a phase and starts the next, in whole
    nanoseconds: inside ``with phases.step():`` the leaves sum to the
    step's wall exactly (time between two phases of a step is the leaf
    ``loop``, which is booked but opens no span).  A step is one more
    span around its leaves, ``raytpu.<layer>.step``.

    A phase or a step may carry attributes (``phase("decode_dispatch",
    seq=7, rows=32)``): small host facts that say WHICH piece of work
    the span is, so a reader can join it to what caused it.  They are
    the span's stats in a trace, under its bare name, on every fragment
    a child splits it into; the table knows nothing of them.

    Cost with no profiler session: one ``perf_counter_ns`` and one empty
    ``TraceAnnotation`` per switch (``tests/test_phases.py`` holds it to
    a per-call budget).  One loop owns a ``Phases``: no lock.
    """

    def __init__(self, layer: str):
        self._prefix = layer + "."
        #: phase -> [times entered, nanoseconds run]
        self._table: Dict[str, List[int]] = {}
        #: (phase, its attributes or None), innermost last
        self._stack: List[tuple] = []
        self._since_ns = 0
        self._ann = None
        self._step_ann = None
        self._step_t0_ns = 0

    # -- the running leaf --------------------------------------------------

    def _suspend(self, now_ns: int) -> None:
        """Book and close the running leaf at `now_ns`."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._stack:
            self._table[self._stack[-1][0]][1] += now_ns - self._since_ns

    def _resume(self, name: str, attrs, now_ns: int,
                entered: int) -> None:
        cell = self._table.get(name)
        if cell is None:
            cell = self._table[name] = [0, 0]
        cell[0] += entered
        self._since_ns = now_ns
        # no span of its own for the loop's fragments: inside a step's
        # span, what no leaf's span covers is the loop
        if name != scopes.LOOP:
            self._ann = _annotation(self._prefix + name, attrs)

    def _push(self, name: str, attrs=None) -> int:
        now_ns = time.perf_counter_ns()
        self._suspend(now_ns)
        self._stack.append((name, attrs))
        self._resume(name, attrs, now_ns, 1)
        return now_ns

    def _pop(self) -> int:
        now_ns = time.perf_counter_ns()
        self._suspend(now_ns)
        self._stack.pop()
        if self._stack:
            self._resume(*self._stack[-1], now_ns, 0)
        return now_ns

    def _begin_step(self, attrs=None) -> int:
        self._step_ann = _annotation(self._prefix + scopes.STEP, attrs)
        self._step_t0_ns = self._push(scopes.LOOP)
        return self._step_t0_ns

    def _end_step(self) -> None:
        now_ns = self._pop()
        wall = now_ns - self._step_t0_ns
        cell = self._table.setdefault(scopes.STEP, [0, 0])
        cell[0] += 1
        cell[1] += wall
        if self._step_ann is not None:
            self._step_ann.__exit__(None, None, None)
            self._step_ann = None

    # -- what callers use --------------------------------------------------

    def phase(self, name: str, **attrs: Any) -> _Phase:
        return _Phase(self, name, attrs or None)

    def step(self, **attrs: Any) -> _Step:
        """One loop iteration: ``raytpu.<layer>.step`` around leaves
        that partition it."""
        return _Step(self, attrs or None)

    def snapshot(self) -> Dict[str, List[float]]:
        """``{phase: [count, seconds]}``; ``step`` counts whole steps."""
        return {name: [n, ns * 1e-9]
                for name, (n, ns) in sorted(self._table.items())}


# ---------------------------------------------------------------------------
# set-up records: where a process's seconds before its first step went
# ---------------------------------------------------------------------------

#: set-up records a process keeps: a record is a small dict, and a
#: process compiles tens to some hundreds of programs (`LAUNCH_HISTORY`
#: of serve/telemetry.py is the same number for the same reason)
SETUP_HISTORY = 4096
#: what a cause may name; a key the stack does not hold reads None
CAUSE_KEYS = ("phase", "program", "signature", "part")

#: module-level, so a reader finds the records after the engine that
#: made them is gone (`serve.telemetry.recent_launches` likewise)
_setup_ring: Deque[Dict[str, Any]] = collections.deque(
    maxlen=SETUP_HISTORY)
_setup_seq = itertools.count()
_causes = threading.local()


def current_cause() -> Optional[Dict[str, Any]]:
    """What this thread is inside of right now: ``{phase, program,
    signature, part}`` as the innermost `cause` block left it, or None
    outside every block."""
    stack = getattr(_causes, "stack", None)
    return dict(stack[-1]) if stack else None


@contextlib.contextmanager
def cause(**fields: Any) -> Iterator[None]:
    """``with cause(phase="params"):`` -- every set-up record this
    thread writes inside the block names it.  Blocks nest: an inner one
    keeps the outer's fields and adds or replaces its own.  The stack
    is per thread, as the compile that a record reports runs on the
    thread that asked for it."""
    stack = getattr(_causes, "stack", None)
    if stack is None:
        stack = _causes.stack = []
    top = stack[-1] if stack else dict.fromkeys(CAUSE_KEYS)
    stack.append({**top, **fields})
    try:
        yield
    finally:
        stack.pop()


def record_setup(kind: str, t0: float, t1: float,
                 **fields: Any) -> Dict[str, Any]:
    """Append one set-up record: its `kind` -- ``compile``
    (_private/compile_cache.py: one for every program JAX hands the
    backend or loads from the persistent cache), ``harvest``
    (device_stats.ProgramRegistry.instrument's side compile of a fresh
    signature) or ``phase`` (`setup_phase`: a leaf of an engine's
    build) --, its ``perf_counter`` stamps (the clock of `Phases` and of
    the launch records), a process-wide ``seq`` (a reader sees a record
    the ring dropped as a gap), this thread's `current_cause` and
    `fields`."""
    record = dict(fields, kind=kind, seq=next(_setup_seq), t0=t0, t1=t1,
                  cause=current_cause())
    _setup_ring.append(record)
    return record


def setup_records(since: Optional[float] = None) -> List[Dict[str, Any]]:
    """This process's set-up records, oldest first, at most
    `SETUP_HISTORY`; with `since`, those closed (``t1``) at or after
    that ``perf_counter`` instant.  The name is where they matter most;
    a compile inside a serving window is recorded all the same, with
    its program and cause."""
    records = list(_setup_ring)
    if since is not None:
        records = [r for r in records if r["t1"] >= since]
    return records


@contextlib.contextmanager
def setup_phase(phases: Phases, name: str) -> Iterator[None]:
    """One leaf ``raytpu.<layer>.<name>`` of a build, as `phases` books
    it; what compiles inside names it as its cause, and on the way out
    the leaf's own stamps become a ``phase`` record."""
    with phases.phase(name) as leaf, cause(phase=name):
        yield
    record_setup("phase", leaf.t0, leaf.t1, phase=name)
