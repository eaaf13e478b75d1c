"""The join launch -> device execution -> fence (PR 39).

The serving engine keeps one record of every program it hands the
device (``ray_tpu/serve/telemetry.py EngineTelemetry.record_launch``):
an engine-wide ``seq``, the program's name as a trace's ``XLA Modules``
line prints it, what it was for (``kind``, ``rows``, a prefill's
``req`` / ``bucket`` / ``n_tail``), the launches in flight ahead of it,
and the ``perf_counter`` stamps of its dispatch and fence phases.  The
dispatch and fence spans carry the ``seq`` as a stat, on the profiler's
clock.  A driver keeps no engine, so the records are asked of the
process (``ray_tpu.serve.telemetry.recent_launches``).

A chip runs one stream in order, so the device's executions of the
recorded programs, in the order they started, are the records in the
order of their ``seq``: k-th to k-th from an anchor.  The anchor is
read, not guessed.  The runtime numbers every execution (``run_id``, a
stat of each ``XLA Modules`` event) and its own host events say which
call enqueued which number (`load_host`): an execution whose enqueuing
call lies inside a record's dispatch phase IS that record's.  With
waves queued ahead the window's first executions were launched before
the profiler was started and have no such call in the trace; they are
counted back from the first execution that has one.  Every other
execution that has one must then sit where the count puts it, names
must agree pair by pair and the records' ``seq`` must run without a
gap: a launch no record accounts for, a record the ring lost or an
execution the trace lost shifts the count against the run ids, the
join gives nothing and says ``joined=false``.  A wrong pairing must
read as a missing metric, never as a number.

The clock.  Host and device stamps of a trace agree to about a
millisecond (``xplane.py``); the lags below are host-minus-device
differences, so a constant offset c of the device's stamps moves one up
and the other down.  The data bound it: ``c >= -min(start - dispatch
t0)`` and ``c <= min(fence t1 - end)`` over the window's launches.
The bracket is printed as found; it is then cut to +-`PRIOR_NS`, what
is known of the profiler's clocks without the data (in a cell whose
device is never idle no launch is tight and the lower side says
nothing), and its midpoint is taken.  Differences between two commits
are sound whatever c is; a split of one gap into two lags is sound to
the bracket's width.

Against a program without launch records (the parent of PR 39) every
reader of the join finds nothing and returns None.  Everything below
the loaders works on plain tuples and dicts, as in ``xplane.py``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import statistics
from typing import Callable, Dict, List, Optional, Sequence, Tuple

from benchmark import estimators
from benchmark.harness import say
from benchmark.reduce import program, xplane
from benchmark.reduce.xplane import Event, Trace

ENGINE_PREFIX = program.ENGINE_PREFIX
YIELD_SPAN = ENGINE_PREFIX + "yield"
DISPATCH_SPANS = (ENGINE_PREFIX + "prefill_dispatch",
                  ENGINE_PREFIX + "decode_dispatch")
#: kinds of launch that prefill a prompt's tokens
PREFILLS = ("prefill", "chunk")
#: what is known of the clocks' offset without the data
PRIOR_NS = 1e6
#: a device that ran nothing for this long before an execution was idle
IDLE_NS = 50e3
#: a host stamp laid over the trace's axis may miss by this
STAMP_NS = 20e3
#: the runtime's host events that tie a call to the run id it enqueued:
#: the call, on the thread that launched; its continuation, which
#: consumes what the call produced (``_c`` = the call's ``_p``), on
#: whatever thread got to it; and inside that the enqueue, which says
#: the ``run_id``
CALL = "tpu::System::Execute"
ISSUE = CALL + "=>IssueSequencedEvent"
ENQUEUE = "DoEnqueueProgram"

#: (name, start_ns, duration_ns, stats)
Span = Tuple[str, float, float, Dict[str, object]]


@dataclasses.dataclass
class Pair:
    """One launch and its execution, every stamp in nanoseconds on the
    trace's axis.  ``start``/``end`` are the execution's as the window
    cut it; ``whole`` says neither edge did.  ``gap_before`` is how
    long the device had run no program of any name when it started
    (None where the window's start hides it).  ``f0``/``f1`` are None
    for a launch the engine never fenced."""
    record: dict
    start: float
    end: float
    whole: bool
    cut_left: bool
    cut_right: bool
    gap_before: Optional[float]
    d0: float
    d1: float
    f0: Optional[float]
    f1: Optional[float]

    @property
    def fused(self) -> bool:
        return bool(self.record.get("fused"))

    @property
    def idle_before(self) -> bool:
        """The device was idle when the execution began."""
        return self.gap_before is not None and self.gap_before > IDLE_NS


@dataclasses.dataclass
class Links:
    """What the runtime's own events say of the first device's stream:
    its executions of every program as (start, end, run id) by start,
    uncut, and for a run id the trace saw enqueued the start of the
    call that did it."""
    runs: List[Tuple[float, float, int]]
    called: Dict[int, float]


@dataclasses.dataclass
class Joined:
    pairs: List[Pair]
    #: executions tied to their record by run id (the others were
    #: counted from those); how the clocks were laid over each other
    #: ("seq": dispatch spans by their stat, "mark": the driver's
    #: window mark)
    linked: int
    how: str
    #: the data's bracket of the device clock's offset, and the offset
    #: taken (midpoint of the bracket cut to +-PRIOR_NS)
    c_lo: Optional[float]
    c_hi: Optional[float]
    c: float
    window_ns: float


# ------------------------------------------------------------- loaders

def load_host(path: str) -> Tuple[List[Span], Links]:
    """The ``raytpu.engine.*`` host spans of an ``.xplane.pb`` with
    their stats (``xplane.load`` keeps names and stamps only), and the
    run ids (`Links`)."""
    from jax.profiler import ProfileData

    spans: List[Span] = []
    runs: List[Tuple[float, float, int]] = []
    calls: Dict[int, float] = {}            # _p -> start
    issues, enqueues = [], []   # (line, start, end, _c), (line, at, run)
    data = ProfileData.from_file(path)
    device = min((p.name for p in data.planes
                  if p.name.startswith("/device:TPU:")), default=None)
    for plane in data.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name != xplane._MODULES_LINE:
                    continue
                for e in line.events:
                    run = dict(e.stats).get("run_id")
                    if run is not None:
                        runs.append((e.start_ns, e.start_ns + e.duration_ns,
                                     run))
        elif plane.name == "/host:CPU":
            for n, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(ENGINE_PREFIX):
                        spans.append((e.name, e.start_ns, e.duration_ns,
                                      dict(e.stats)))
                    elif e.name == CALL:
                        calls[dict(e.stats).get("_p")] = e.start_ns
                    elif e.name == ISSUE:
                        issues.append((n, e.start_ns,
                                       e.start_ns + e.duration_ns,
                                       dict(e.stats).get("_c")))
                    elif e.name == ENQUEUE:
                        enqueues.append((n, e.start_ns,
                                         dict(e.stats).get("run_id")))
    spans.sort(key=lambda s: s[1])
    runs.sort()
    return spans, Links(runs, _called(calls, issues, enqueues))


def _called(calls, issues, enqueues) -> Dict[int, float]:
    """run id -> start of the call that enqueued it: the enqueue lies
    inside a continuation on its thread, and the continuation names
    the call."""
    by_line = collections.defaultdict(list)
    for k, s, e, c in issues:
        by_line[k].append((s, e, c))
    for stretch in by_line.values():
        stretch.sort()
    called = {}
    for k, s, run in enqueues:
        stretch = by_line.get(k, [])
        at = bisect.bisect_right(stretch, (s, float("inf"), 0)) - 1
        if at >= 0 and stretch[at][1] >= s and stretch[at][2] in calls:
            called[run] = calls[stretch[at][2]]
    return called


def launch_records() -> Optional[List[dict]]:
    """The newest engine's landed launch records, by ``seq``; None
    against a program that keeps none."""
    try:
        from ray_tpu.serve.telemetry import recent_launches
    except ImportError:
        return None
    return sorted(recent_launches(), key=lambda r: r["seq"]) or None


# --------------------------------------------------------------- clock

def host_to_trace(records: Sequence[dict], spans: Sequence[Span],
                  mark: Optional[Tuple[float, float]]
                  ) -> Optional[Tuple[Callable[[float], float], str]]:
    """``to_ns(perf_counter seconds) -> nanoseconds on the trace's
    axis`` and how it was found: from the dispatch spans that carry a
    record's ``seq`` (the span's start is the record's ``dispatch``
    t0, taken by the same call), else from the driver's mark `mark` =
    (trace ns, host seconds) of ``bench.window_start``."""
    by_seq = {s[3]["seq"]: s[1] for s in spans
              if s[0] in DISPATCH_SPANS and "seq" in s[3]}
    deltas = [by_seq[r["seq"]] - r["dispatch"][0] * 1e9
              for r in records
              if r["seq"] in by_seq and not r.get("fused")]
    if deltas:
        delta, how = statistics.median(deltas), "seq"
    elif mark is not None:
        delta, how = mark[0] - mark[1] * 1e9, "mark"
    else:
        return None
    return (lambda t: t * 1e9 + delta), how


# ---------------------------------------------------------------- join

def _executions(trace: Trace, names) -> List[Event]:
    """The first device's executions of the programs `names`, by start."""
    return sorted((e for e in trace.devices[0].modules
                   if xplane.module_name(e[0]) in names),
                  key=lambda e: e[1])


def _gap_before(trace: Trace) -> Callable[[float], Optional[float]]:
    """For how long the first device had run no program of any name
    before a stamp: back to the end of the last execution that began
    before it, or to the window's start; None within `IDLE_NS` of the
    window's start, where the window hides what ran."""
    runs = sorted((s, s + d) for _, s, d in trace.devices[0].modules)
    starts = [s for s, _ in runs]
    ends_so_far, hi = [], trace.t0_ns
    for _, e in runs:
        hi = max(hi, e)
        ends_so_far.append(hi)

    def gap(stamp: float) -> Optional[float]:
        if stamp - trace.t0_ns <= IDLE_NS:
            return None
        k = bisect.bisect_left(starts, stamp) - 1
        return stamp - (ends_so_far[k] if k >= 0 else trace.t0_ns)

    return gap


def _record_of(events: Sequence[Event], links: Links, d0s, d1s
               ) -> List[Optional[int]]:
    """Per execution the index of the record whose dispatch phase holds
    the call that enqueued it; None where the trace has no such call
    (it was made before the profiler was started) or no record holds
    it."""
    starts = [s for s, _, _ in links.runs]
    out: List[Optional[int]] = []
    for _, s, _ in events:
        # the window may have cut the execution's start: the run that
        # was under way at `s`
        at = bisect.bisect_right(starts, s) - 1
        call = links.called.get(links.runs[at][2]) \
            if at >= 0 and links.runs[at][1] > s else None
        i = None
        if call is not None:
            i = bisect.bisect_right(d0s, call + STAMP_NS) - 1
            if i < 0 or call > d1s[i] + STAMP_NS:
                i = None
        out.append(i)
    return out


def _bracket(pairs: Sequence[Pair]
             ) -> Tuple[Optional[float], Optional[float], float]:
    """(lo, hi, c): the data's bounds on the offset c to add to a
    device stamp (either may be None where nothing bounds that side),
    and the midpoint of the bracket cut to +-PRIOR_NS."""
    launch = [p.start - p.d0 for p in pairs if not p.cut_left]
    back = [p.f1 - p.end for p in pairs
            if p.f1 is not None and not p.cut_right]
    lo = -min(launch) if launch else None
    hi = min(back) if back else None
    cut_lo = max(-PRIOR_NS, lo if lo is not None else -PRIOR_NS)
    cut_hi = min(PRIOR_NS, hi if hi is not None else PRIOR_NS)
    if cut_lo > cut_hi:         # the data outside what was known
        cut_lo, cut_hi = (lo if lo is not None else cut_hi,
                          hi if hi is not None else cut_lo)
    return lo, hi, (cut_lo + cut_hi) / 2.0


def join(records: Sequence[dict], trace: Trace, spans: Sequence[Span],
         links: Links, mark: Optional[Tuple[float, float]] = None,
         why: Optional[List[str]] = None) -> Optional[Joined]:
    """The window's executions paired with their launch records, or
    None (and the reason appended to `why`)."""
    def no(reason: str):
        if why is not None:
            why.append(reason)
        return None

    if not records:
        return no("no launch records")
    clock = host_to_trace(records, spans, mark)
    if clock is None:
        return no("no dispatch span with a seq and no window mark")
    to_ns, how = clock
    events = _executions(trace, {r["program"] for r in records})
    if not events:
        return no("no execution of a recorded program in the window")
    d0s = [to_ns(r["dispatch"][0]) for r in records]
    d1s = [to_ns(r["dispatch"][1]) for r in records]
    tied = _record_of(events, links, d0s, d1s)
    # k-th to k-th: execution k is record k + shift, and every
    # execution the run ids tie to a record must say the same shift
    shifts = collections.Counter(i - k for k, i in enumerate(tied)
                                 if i is not None)
    if not shifts:
        return no(f"none of {len(events)} executions has a run id that "
                  f"a call inside a record's dispatch enqueued")
    if len(shifts) > 1:
        return no(f"the count and the run ids disagree, {dict(shifts)} "
                  f"(records off: executions): a launch without a "
                  f"record, a record the ring lost or an execution the "
                  f"trace lost")
    (shift, linked), = shifts.items()
    if shift < 0 or shift + len(events) > len(records):
        return no(f"{len(events)} executions from record {shift} on, of "
                  f"{len(records)} records: the ring lost some")
    mine = records[shift:shift + len(events)]
    if [r["seq"] for r in mine] != list(range(mine[0]["seq"],
                                              mine[0]["seq"] + len(mine))):
        return no("the records' seq has a gap: the ring lost some")
    gap = _gap_before(trace)
    t0, t1 = trace.t0_ns, trace.t1_ns
    pairs = []
    for k, (r, (name, s, d)) in enumerate(zip(mine, events), shift):
        if xplane.module_name(name) != r["program"]:
            return no(f"seq {r['seq']} launched {r['program']} and the "
                      f"count gives it {xplane.module_name(name)}")
        f0, f1 = (to_ns(t) for t in r["fence"]) if r["fence"] \
            else (None, None)
        cut_left, cut_right = s <= t0, s + d >= t1
        pairs.append(Pair(r, s, s + d, not (cut_left or cut_right),
                          cut_left, cut_right, gap(s), d0s[k], d1s[k],
                          f0, f1))
    lo, hi, c = _bracket(pairs)
    return Joined(pairs, linked, how, lo, hi, c, t1 - t0)


# ------------------------------------------------------------- metrics

def prefill_device_ms_per_ktoken(j: Joined) -> Optional[float]:
    """Device milliseconds of the whole prefill and chunk executions
    per thousand prompt tokens they prefilled."""
    got = [p for p in j.pairs if p.record["kind"] in PREFILLS and p.whole]
    tokens = sum(p.record["n_tail"] for p in got)
    if not tokens:
        return None
    return sum(p.end - p.start for p in got) / 1e6 / tokens * 1e3


def decode_rows_stalled_share(j: Joined, max_slots: int
                              ) -> Optional[float]:
    """Slot-seconds decoding rows stood behind a prefill or a chunk
    over the window's slot-seconds, %."""
    got = [p for p in j.pairs if p.record["kind"] in PREFILLS]
    if not got or not max_slots:
        return None
    stalled = sum((p.end - p.start) * p.record["rows"] for p in got)
    return 100.0 * stalled / (j.window_ns * max_slots)


def prefill_queued_ms(j: Joined) -> List[float]:
    """Per prefill with a dispatch phase of its own: device start
    behind the end of its dispatch, ms."""
    return [(p.start + j.c - p.d1) / 1e6 for p in j.pairs
            if p.record["kind"] in PREFILLS and not p.fused
            and not p.cut_left]


def launch_lag_ms(j: Joined) -> List[float]:
    """Per launch whose execution began on an idle device: its start
    behind the end of its dispatch, ms; or, where the dispatch was
    over before the device fell idle (the launch was queued and the
    device waited all the same), behind the end of the execution
    before it: how long an idle device waited for a launch already
    made."""
    return [min(p.start + j.c - p.d1, p.gap_before) / 1e6 for p in j.pairs
            if p.idle_before and not p.fused and not p.cut_left]


def fence_return_lag_ms(j: Joined) -> List[float]:
    """Per fence that waited for its program (it began before the
    program ended): its return behind the program's end, ms."""
    return [(p.f1 - (p.end + j.c)) / 1e6 for p in j.pairs
            if p.f1 is not None and not p.fused and not p.cut_right
            and p.f0 < p.end + j.c]


def holds(spans: Sequence[Span], t0: float, t1: float
          ) -> List[Tuple[float, float, float]]:
    """(start, end, held ns) of every stretch between the end of one
    ``raytpu.engine.yield`` and the start of the next inside the
    window: the engine's loop ran and let no caller in.  Held is the
    part of the stretch under a ``raytpu.engine.step`` (a parked
    engine holds nobody up: it is between steps)."""
    yields = sorted((s, s + d) for name, s, d, _ in spans
                    if name == YIELD_SPAN and s >= t0 and s + d <= t1)
    steps = xplane.union((s, s + d) for name, s, d, _ in spans
                         if name == program.STEP_SPAN)
    return [(a, b, program.overlap_ns([(a, b)], steps))
            for (_, a), (b, _) in zip(yields, yields[1:]) if b > a]


def engine_hold_ms(spans: Sequence[Span], t0: float, t1: float,
                   q: float) -> Optional[float]:
    held = [h / 1e6 for _, _, h in holds(spans, t0, t1)]
    return estimators.percentile(held, q) if held else None


# ------------------------------------------------------------ one run

def _host_of(run) -> Tuple[List[Span], Links]:
    def make():
        try:
            return load_host(xplane.find_xplane(run.ctx.trace_dir))
        except (FileNotFoundError, ValueError):
            return [], Links([], {})

    return program._cached(run, "launch_host", make)


def _idle_inside(trace: Trace, pairs: Sequence[Pair]) -> float:
    """Nanoseconds inside the paired executions in which no operation
    ran on the device: stalls within a program, which a device's idle
    share counts and no lag around the program explains."""
    runs = xplane.union((p.start, p.end) for p in pairs)
    busy = xplane.busy_intervals(trace.devices[0])
    return xplane.total(runs) - program.overlap_ns(runs, busy)


def _longest_holds(trace: Trace, spans, joined: Joined, n: int = 3):
    """The n longest holds as [ms, prefills, waves]: what the records
    (by the stamps of their dispatch) say the loop launched inside."""
    out = []
    for a, b, h in sorted(holds(spans, trace.t0_ns, trace.t1_ns),
                          key=lambda x: -x[2])[:n]:
        inside = [p.record["kind"] for p in joined.pairs if a <= p.d0 < b]
        out.append([round(h / 1e6, 3),
                    sum(k in PREFILLS for k in inside),
                    sum(k not in PREFILLS for k in inside)])
    return out


def joined_run(run) -> Optional[Joined]:
    """`join` of one run, once; printed as the line ``[launches]``."""
    trace = getattr(run, "trace", None)
    if trace is None:
        return None

    def make():
        records = launch_records()
        if records is None:
            return None
        spans, links = _host_of(run)
        mark = (trace.t0_ns, run.trace_t0) \
            if getattr(run, "trace_t0", None) is not None else None
        why: List[str] = []
        j = join(records, trace, spans, links, mark, why)
        if j is None:
            say("launches", joined=False, why=why[0],
                records=len(records))
            return None
        kinds = collections.Counter(p.record["kind"] for p in j.pairs)
        buckets = collections.Counter(
            p.record["bucket"] for p in j.pairs
            if p.record["kind"] in PREFILLS)
        ahead = [p.record["ahead"] for p in j.pairs
                 if p.record["kind"] in PREFILLS]
        say("launches", joined=True, clocks=j.how,
            by_run_id=[j.linked, len(j.pairs)], records=len(records),
            pairs=dict(sorted(kinds.items())),
            cut_by_an_edge=sum(not p.whole for p in j.pairs),
            prefill_buckets={str(b): n for b, n in sorted(buckets.items())},
            prefill_ahead_mean=round(sum(ahead) / len(ahead), 2)
            if ahead else None,
            on_idle_device=sum(p.idle_before for p in j.pairs),
            # what the two lags' medians are medians of, and the idle
            # they cannot explain: stalls inside the programs
            launch_lag_ms_n_mean=_n_mean(launch_lag_ms(j)),
            fence_return_lag_ms_n_mean=_n_mean(fence_return_lag_ms(j)),
            idle_inside_executions_ms=_ms(_idle_inside(trace, j.pairs)),
            bracket_ms=[_ms(j.c_lo), _ms(j.c_hi)],
            bracket_width_ms=_ms(j.c_hi - j.c_lo)
            if None not in (j.c_lo, j.c_hi) else None,
            offset_ms=_ms(j.c),
            longest_holds_ms_prefills_waves=_longest_holds(trace, spans, j))
        return j

    return program._cached(run, "launches", make)


def _ms(ns: Optional[float]) -> Optional[float]:
    return None if ns is None else round(ns / 1e6, 4)


def _n_mean(xs: Sequence[float]) -> List[float]:
    return [len(xs), round(sum(xs) / len(xs), 4) if xs else None]


def _median(xs: Sequence[float]) -> Optional[float]:
    return estimators.percentile(xs, 50) if xs else None


def read_prefill_device_ms_per_ktoken(run) -> Optional[float]:
    j = joined_run(run)
    return None if j is None else prefill_device_ms_per_ktoken(j)


def read_decode_rows_stalled_share(run) -> Optional[float]:
    j = joined_run(run)
    return None if j is None \
        else decode_rows_stalled_share(j, run.engine.max_slots)


def read_prefill_queued_p50_ms(run) -> Optional[float]:
    j = joined_run(run)
    return None if j is None else _median(prefill_queued_ms(j))


def read_launch_lag_p50_ms(run) -> Optional[float]:
    j = joined_run(run)
    return None if j is None else _median(launch_lag_ms(j))


def read_fence_return_lag_p50_ms(run) -> Optional[float]:
    j = joined_run(run)
    return None if j is None else _median(fence_return_lag_ms(j))


def read_engine_hold_p95_ms(run) -> Optional[float]:
    trace = getattr(run, "trace", None)
    if trace is None or launch_records() is None:
        return None
    return engine_hold_ms(_host_of(run)[0], trace.t0_ns, trace.t1_ns, 95)
