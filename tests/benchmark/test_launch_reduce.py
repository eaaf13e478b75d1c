"""``benchmark/reduce/launches.py`` on synthetic records and events: the
join of launch records to device executions by the runtime's run ids,
the clock bracket, the eight per-layer metrics' hand-computed values,
and what must read as a missing metric: a pairing that cannot be
shown."""

import os
import types

import pytest

from benchmark import cells
from benchmark.reduce import launches as L
from benchmark.reduce.xplane import DeviceTrace, windowed

MS = 1e6
#: perf_counter seconds at the trace axis' zero
HOST0 = 5000.0
MARKS = [("bench.window_start", 0.0, 1000.0), ("bench.window_end",
                                               1000 * MS, 1000.0)]
NEW = ("prefill_device_ms_per_ktoken.offline",
       "decode_rows_stalled_share.offline", "prefill_queued_p50_ms.offline",
       "launch_lag_p50_ms.chat", "launch_lag_p50_ms.offline",
       "fence_return_lag_p50_ms.chat", "fence_return_lag_p50_ms.offline",
       "engine_hold_p95_ms.chat")


def host(ms: float) -> float:
    """The perf_counter stamp of `ms` on the trace's axis."""
    return HOST0 + ms / 1e3


def record(seq, kind, dispatch, fence, program="jit_pool_step", rows=4,
           ahead=0, **facts):
    rec = dict(seq=seq, kind=kind, program=program, rows=rows, ahead=ahead,
               dispatch=tuple(host(t) for t in dispatch),
               fence=None if fence is None
               else tuple(host(t) for t in fence), **facts)
    if rec.get("fused"):
        rec["fence"] = rec["dispatch"]
    return rec


def execution(program, start_ms, dur_ms, skew_ms=0.0):
    return (f"{program}(77)", (start_ms + skew_ms) * MS, dur_ms * MS)


def dispatch_span(rec, leaf):
    d0, d1 = ((t - HOST0) * 1e9 for t in rec["dispatch"])
    return (L.ENGINE_PREFIX + leaf, d0, d1 - d0, {"seq": rec["seq"]})


OTHER = ("jit__threefry_split(9)", 5 * MS, 0.01 * MS)


def trace_of(events, marks=MARKS):
    dev = DeviceTrace("/device:TPU:0", [], sorted(
        list(events) + [OTHER], key=lambda e: e[1]))
    return windowed([dev], list(marks))


def links_of(records, events, profiled_from_ms=float("-inf")):
    """The run ids as the runtime gives them: every execution of the
    stream numbered in order, the k-th of `events` enqueued by a call
    0.1 ms into the k-th record's dispatch.  Calls made before
    `profiled_from_ms` are not in the trace."""
    stream = sorted(list(events) + [OTHER], key=lambda e: e[1])
    run_of = {e: 100 + k for k, e in enumerate(stream)}
    called = {}
    for r, e in zip(records, events):
        at = (r["dispatch"][0] - HOST0) * 1e9 + 0.1 * MS
        if at >= profiled_from_ms * MS:
            called[run_of[e]] = at
    return L.Links([(s, s + d, run_of[(n, s, d)]) for n, s, d in stream],
                   called)


PREFILL = "jit_paged_prefill_sample"


def fenced_world(skew_ms=0.0):
    """An engine that fences every launch before the next (a 100 ms
    step): dispatch 0.2 ms, the program starts 0.3 ms behind it, the
    fence returns 0.5 ms behind the program's end.  Two decode waves, a
    prefill of 100 tokens behind 3 rows, a chunk of 50 behind 2 (one
    phase: fused).  `skew_ms` is what the device's clock reads late."""
    records = [
        record(1, "decode", (10, 10.2), (10.3, 111.0)),
        record(2, "prefill", (112, 112.2), (112.3, 163.0), PREFILL, rows=3,
               req=7, bucket=128, prefix_len=0, n_tail=100),
        record(3, "decode", (164, 164.2), (164.3, 265.0)),
        record(4, "chunk", (266, 297), None, PREFILL, rows=2, req=8,
               bucket=64, prefix_len=64, n_tail=50, fused=True),
        record(5, "decode", (298, 298.2), (298.3, 399.0)),
    ]
    events = [execution("jit_pool_step", 10.5, 100, skew_ms),
              execution(PREFILL, 112.5, 50, skew_ms),
              execution("jit_pool_step", 164.5, 100, skew_ms),
              execution(PREFILL, 266.5, 30, skew_ms),
              execution("jit_pool_step", 298.5, 100, skew_ms)]
    spans = [dispatch_span(records[0], "decode_dispatch"),
             dispatch_span(records[1], "prefill_dispatch"),
             dispatch_span(records[2], "decode_dispatch"),
             dispatch_span(records[4], "decode_dispatch"),
             (L.program.STEP_SPAN, 9 * MS, 400 * MS, {"n": 1})]
    for end in (111.2, 163.2, 265.2, 297.5):
        spans.append((L.YIELD_SPAN, end * MS, 0.05 * MS, {}))
    return (records, events, sorted(spans, key=lambda s: s[1]),
            links_of(records, events))


def test_each_metric_reads_its_hand_computed_value():
    records, events, spans, links = fenced_world()
    trace = trace_of(events)
    j = L.join(records, trace, spans, links)
    assert j is not None and j.how == "seq" and j.linked == 5
    assert [p.record["seq"] for p in j.pairs] == [1, 2, 3, 4, 5]
    assert all(p.whole and p.idle_before for p in j.pairs)
    # 50 ms for 100 tokens and 30 ms for 50
    assert L.prefill_device_ms_per_ktoken(j) == pytest.approx(
        80.0 / 150 * 1e3)
    # (50 ms x 3 rows + 30 ms x 2 rows) of 1,000 ms x 4 slots
    assert L.decode_rows_stalled_share(j, 4) == pytest.approx(5.25)
    # the chunk has no dispatch phase of its own: one prefill
    assert L.prefill_queued_ms(j) == pytest.approx([0.3])
    assert L.launch_lag_ms(j) == pytest.approx([0.3] * 4)
    assert L.fence_return_lag_ms(j) == pytest.approx([0.5] * 4)
    # stretches between yields: 51.95, 101.95, 32.25 ms, all in a step
    held = [h / MS for _, _, h in L.holds(spans, trace.t0_ns, trace.t1_ns)]
    assert held == pytest.approx([51.95, 101.95, 32.25])
    assert L.engine_hold_ms(spans, trace.t0_ns, trace.t1_ns, 95) \
        == pytest.approx(51.95 + 0.9 * 50.0)


def test_a_parked_engine_holds_nobody_up():
    """Between two steps the loop waits for work: that is no hold."""
    spans = [(L.program.STEP_SPAN, 0.0, 100 * MS, {}),
             (L.YIELD_SPAN, 99 * MS, 0.5 * MS, {}),
             (L.program.STEP_SPAN, 600 * MS, 50 * MS, {}),
             (L.YIELD_SPAN, 649 * MS, 0.5 * MS, {})]
    (_, _, held), = L.holds(spans, 0.0, 1000 * MS)
    assert held / MS == pytest.approx(0.5 + 49.0)
    assert L.engine_hold_ms([], 0.0, 1000 * MS, 95) is None


@pytest.mark.parametrize("skew_ms", [0.0, 0.2, -0.3])
def test_the_clock_bracket_is_found_and_applied(skew_ms):
    """A device clock that reads `skew_ms` late moves the raw launch
    gap up and the raw return gap down; the bracket's midpoint takes it
    out again."""
    records, events, spans, links = fenced_world(skew_ms)
    j = L.join(records, trace_of(events), spans, links)
    # no start before its dispatch began (0.5 ms behind d0 at the
    # least), no fence back before its program's end (0.5 ms)
    assert j.c_lo / MS == pytest.approx(-(0.5 + skew_ms))
    assert j.c_hi / MS == pytest.approx(0.5 - skew_ms)
    assert j.c / MS == pytest.approx(-skew_ms)
    assert L.launch_lag_ms(j) == pytest.approx([0.3] * 4)
    assert L.fence_return_lag_ms(j) == pytest.approx([0.5] * 4)
    assert L.prefill_queued_ms(j) == pytest.approx([0.3])


def test_a_side_that_bounds_nothing_is_cut_to_what_is_known():
    """With waves queued ahead no launch is tight: the data's lower
    bound is a whole queue away, and the offset taken lies within what
    the profiler's clocks are known to hold."""
    records, events, spans, links = queued_world()
    j = L.join(records, trace_of(events), spans, links)
    assert j.c_lo < -20 * MS and 0 < j.c_hi <= 0.5 * MS
    assert -L.PRIOR_NS <= j.c <= j.c_hi


def queued_world(n=40, depth=4, wave_ms=10.0, first=-75.0,
                 profiled_from_ms=0.0):
    """An engine that keeps `depth` waves queued on a device that is
    never idle: wave k runs [first + 10k, +10), its fence returns 0.3
    ms behind its end, and wave k + depth is dispatched right after.  A
    prefill of 20 ms stands in the stream after the twelfth wave,
    dispatched behind the waves in flight.  The window opens inside one
    execution and closes inside another; the profiler was started at
    `profiled_from_ms`, so the waves queued before it run inside the
    window and the trace has no call of theirs."""
    records, events = [], []
    t_dev, seq = first, 0
    ends = []
    for k in range(n):
        if k == 12:
            seq += 1
            d0 = ends[k - depth] + 0.35 if k >= depth else t_dev - 1
            records.append(record(
                seq, "prefill", (d0, d0 + 0.2), (t_dev + 20.1, t_dev + 20.3),
                PREFILL, rows=4, ahead=depth, req=3, bucket=64,
                prefix_len=0, n_tail=40))
            events.append(execution(PREFILL, t_dev, 20.0))
            t_dev += 20.0
        seq += 1
        d0 = ends[k - depth] + 0.6 if k >= depth else first - 5 + k * 0.5
        ends.append(t_dev + wave_ms)
        records.append(record(seq, "decode", (d0, d0 + 0.2),
                              (t_dev + 0.1, t_dev + wave_ms + 0.3),
                              ahead=min(k, depth)))
        events.append(execution("jit_pool_step", t_dev, wave_ms))
        t_dev += wave_ms
    spans = [dispatch_span(r, "decode_dispatch" if r["kind"] == "decode"
                           else "prefill_dispatch") for r in records]
    return (records, events, sorted(spans, key=lambda s: s[1]),
            links_of(records, events, profiled_from_ms))


def test_waves_queued_ahead_are_counted_back_from_the_first_run_id():
    records, events, spans, links = queued_world()
    marks = [("bench.window_start", 0.0, 1000.0),
             ("bench.window_end", 300 * MS, 1000.0)]
    trace = trace_of(events, marks)
    j = L.join(records, trace, spans, links)
    assert j is not None
    # the executions that ended before the window opened are gone, and
    # so are their records: the first pair is the wave the window cut
    first = j.pairs[0]
    assert first.cut_left and not first.whole
    assert first.record["seq"] == 8
    # the four waves dispatched before the profiler was started have no
    # call in the trace: counted back from the fifth, which has
    assert j.linked == len(j.pairs) - 4
    assert j.pairs[-1].cut_right
    for p in j.pairs:
        if p.whole and p.f1 is not None:
            assert (p.f1 - p.end) / MS == pytest.approx(0.3)
    # the prefill waited behind the waves in flight: it was dispatched
    # at 15.55 ms and ran at 45, a queue and not a lag
    (queued,) = L.prefill_queued_ms(j)
    assert queued == pytest.approx(45 - 15.55 + j.c / MS)
    # a device that is never idle has no launch lag to read
    assert L.launch_lag_ms(j) == []
    assert L.decode_rows_stalled_share(j, 4) == pytest.approx(
        100.0 * 20 * 4 / (300 * 4))


def test_among_waves_alone_the_run_ids_tell_the_pairings_apart():
    """A window of decode waves only, four queued ahead: a pairing
    shifted by one, two or three waves has the same names and breaks no
    inequality (each wave is launched 40 ms before it runs); the run
    ids give the true one, in which every fence returns just behind its
    program."""
    records, events, spans, links = queued_world()
    marks = [("bench.window_start", 150 * MS, 1000.0),
             ("bench.window_end", 300 * MS, 1000.0)]
    j = L.join(records, trace_of(events, marks), spans, links)
    assert {p.record["kind"] for p in j.pairs} == {"decode"}
    assert j.linked == len(j.pairs)
    for p in j.pairs:
        if not p.cut_right:
            assert (p.f1 - p.end) / MS == pytest.approx(0.3)
    assert [p.record["seq"] for p in j.pairs] == list(range(
        j.pairs[0].record["seq"], j.pairs[0].record["seq"] + len(j.pairs)))


def test_a_queued_launch_an_idle_device_waited_for_reads_the_wait():
    """Dispatched 50 ms before it ran, behind a wave; the device stood
    idle 0.4 ms before it all the same.  What it waited is the 0.4 ms,
    not the queue."""
    facts = dict(whole=True, cut_left=False, cut_right=False,
                 f0=60 * MS, f1=111 * MS)
    queued = L.Pair({"kind": "decode"}, 100 * MS, 110 * MS,
                    gap_before=0.4 * MS, d0=50 * MS, d1=50.2 * MS, **facts)
    starved = L.Pair({"kind": "decode"}, 100 * MS, 110 * MS,
                     gap_before=7 * MS, d0=99.5 * MS, d1=99.7 * MS, **facts)
    busy = L.Pair({"kind": "decode"}, 100 * MS, 110 * MS,
                  gap_before=0.002 * MS, d0=50 * MS, d1=50.2 * MS, **facts)
    j = L.Joined([queued, starved, busy], 3, "seq", None, None, 0.0,
                 1000 * MS)
    assert L.launch_lag_ms(j) == pytest.approx([0.4, 0.3])
    assert not busy.idle_before


def test_stalls_inside_an_execution_are_told_apart_from_the_lags():
    records, events, spans, links = fenced_world()
    ops = [("%fusion.1 = bf16[8]{0} fusion(%x)", s + 2 * MS, d - 5 * MS)
           for _, s, d in events]
    dev = DeviceTrace("/device:TPU:0", ops, sorted(events,
                                                   key=lambda e: e[1]))
    trace = windowed([dev], list(MARKS))
    j = L.join(records, trace, spans, links)
    assert L._idle_inside(trace, j.pairs) / MS == pytest.approx(5 * 5.0)


def test_a_record_that_ended_just_before_the_window_is_skipped():
    """The join goes from the window's executions to their records,
    so a record that ended before it opened is never asked for."""
    records, events, spans, links = fenced_world()
    marks = [("bench.window_start", 111.5 * MS, 1000.0),
             ("bench.window_end", 1000 * MS, 1000.0)]
    j = L.join(records, trace_of(events, marks), spans, links)
    assert [p.record["seq"] for p in j.pairs] == [2, 3, 4, 5]


def test_a_window_edge_that_cuts_one_launch_still_joins():
    records, events, spans, links = fenced_world()
    marks = [("bench.window_start", 130 * MS, 1000.0),
             ("bench.window_end", 350 * MS, 1000.0)]
    j = L.join(records, trace_of(events, marks), spans, links)
    assert [p.record["seq"] for p in j.pairs] == [2, 3, 4, 5]
    cut = [p.record["seq"] for p in j.pairs if not p.whole]
    assert cut == [2, 5]
    # a cut prefill stalls rows for what the window holds of it (32.5
    # of its 50 ms) and stays out of the cost per token
    assert L.decode_rows_stalled_share(j, 4) == pytest.approx(
        100.0 * (32.5 * 3 + 30 * 2) / (220 * 4))
    assert L.prefill_device_ms_per_ktoken(j) == pytest.approx(30 / 50 * 1e3)
    assert L.prefill_queued_ms(j) == []


def fake_run(records, events, spans, links, monkeypatch, marks=MARKS):
    trace = trace_of(events, marks)
    run = types.SimpleNamespace(
        trace=trace, trace_t0=host(trace.t0_ns / MS),
        engine=types.SimpleNamespace(max_slots=4),
        ctx=types.SimpleNamespace(trace_dir="/nonexistent"))
    monkeypatch.setattr(L, "launch_records", lambda: records)
    monkeypatch.setattr(L, "_host_of", lambda run: (spans, links))
    return run


READERS = (L.read_prefill_device_ms_per_ktoken,
           L.read_decode_rows_stalled_share, L.read_prefill_queued_p50_ms,
           L.read_launch_lag_p50_ms, L.read_fence_return_lag_p50_ms)


def test_the_readers_print_one_line_and_agree_with_the_join(
        monkeypatch, capsys):
    records, events, spans, links = fenced_world()
    run = fake_run(records, events, spans, links, monkeypatch)
    got = [read(run) for read in READERS]
    assert got == pytest.approx([80.0 / 150 * 1e3, 5.25, 0.3, 0.3, 0.5])
    assert L.read_engine_hold_p95_ms(run) == pytest.approx(96.95)
    out = capsys.readouterr().out
    assert out.count("[launches]") == 1 and "joined=true" in out
    assert 'pairs={"chunk": 1, "decode": 3, "prefill": 1}' in out
    assert "by_run_id=[5, 5]" in out
    assert 'prefill_buckets={"64": 1, "128": 1}' in out
    assert "bracket_width_ms=1.0" in out
    # the longest hold (101.95 ms) launched one wave and no prefill
    assert "longest_holds_ms_prefills_waves=[[101.95, 0, 1]" in out


def test_without_span_stats_the_clocks_meet_at_the_window_mark(
        monkeypatch, capsys):
    records, events, spans, links = fenced_world()
    bare = [(name, s, d, {}) for name, s, d, _ in spans]
    run = fake_run(records, events, bare, links, monkeypatch)
    assert L.read_fence_return_lag_p50_ms(run) == pytest.approx(0.5)
    assert 'clocks="mark"' in capsys.readouterr().out


@pytest.mark.parametrize("world", ["fenced", "queued"])
@pytest.mark.parametrize("fault", [
    "two_records_lost", "two_executions_lost", "one_record_lost",
    "one_launch_never_recorded"])
def test_a_count_mismatch_reads_as_missing_never_as_a_number(
        world, fault, monkeypatch, capsys):
    records, events, spans, links = fenced_world() if world == "fenced" \
        else queued_world()
    marks = MARKS if world == "fenced" else [
        ("bench.window_start", 0.0, 1000.0),
        ("bench.window_end", 300 * MS, 1000.0)]
    mid = len(records) // 2
    if fault == "two_records_lost":
        records = records[:mid - 1] + records[mid + 1:]
    elif fault == "one_record_lost":
        records = records[:mid] + records[mid + 1:]
    elif fault == "one_launch_never_recorded":
        # a site that hands the device a program and stamps nothing:
        # the seq has no gap, and every pair behind it is one off
        records = [dict(r, seq=r["seq"] - (k >= mid))
                   for k, r in enumerate(records) if k != mid]
        spans = [(n, s0, d, dict(st, seq=st["seq"] - (st["seq"] > mid))
                  if "seq" in st else st) for n, s0, d, st in spans
                 if st.get("seq") != mid + 1]
    else:
        # the trace lost them; the runtime had numbered them
        at = len(events) // 2
        lost = {e[1] for e in events[at - 1:at + 1]}
        events = events[:at - 1] + events[at + 1:]
        links = L.Links([r for r in links.runs if r[0] not in lost],
                        links.called)
    run = fake_run(records, events, spans, links, monkeypatch, marks)
    assert [read(run) for read in READERS] == [None] * len(READERS)
    out = capsys.readouterr().out
    assert out.count("[launches]") == 1 and "joined=false" in out


def test_a_program_without_launch_records_gives_none(monkeypatch):
    from ray_tpu.serve import telemetry

    records, events, spans, links = fenced_world()
    run = fake_run(records, events, spans, links, monkeypatch)
    monkeypatch.undo()
    monkeypatch.delattr(telemetry, "recent_launches")   # the parent
    assert L.launch_records() is None
    monkeypatch.setattr(L, "_host_of", lambda run: (spans, links))
    assert [read(run) for read in READERS] == [None] * len(READERS)
    assert L.read_engine_hold_p95_ms(run) is None
    # and an engine that launched nothing, or an untraced run
    monkeypatch.undo()
    monkeypatch.setattr(telemetry, "recent_launches", lambda: [])
    assert L.launch_records() is None
    assert L.read_launch_lag_p50_ms(types.SimpleNamespace(trace=None)) \
        is None


def test_records_are_read_from_the_process_by_seq(monkeypatch):
    from ray_tpu.serve import telemetry

    tel = telemetry.EngineTelemetry("t_launch_reduce")
    for seq in (2, 1, 3):
        tel.record_launch(dict(seq=seq, kind="decode", rows=1, ahead=0,
                               program="jit_pool_step",
                               dispatch=(1.0, 1.1), fence=(1.2, 1.3)))
    assert [r["seq"] for r in L.launch_records()] == [1, 2, 3]


@pytest.mark.parametrize("name", NEW)
def test_a_new_entry_names_what_the_benchmark_already_has(name):
    bench = cells.load_benchmark()
    entries = bench["per_layer"]
    (entry,) = [m for m in entries if m["name"] == name]
    older = entries[:min(i for i, m in enumerate(entries)
                         if m["name"] in NEW)]
    assert entry in entries[len(older):]           # appended, not put in
    assert entry["layer"] in {m["layer"] for m in older}
    moved = {m["name"]: m for m in bench["end_to_end"]}[entry["moves"]]
    assert set(entry["workloads"]) <= set(moved["workloads"])
    assert entry["source"] == "program_span" and entry["unit"] in ("ms", "%")
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    # a reader of its own, by the name before the suffix
    base = name.rsplit(".", 1)[0]
    assert os.path.isfile(os.path.join(cells.HERE, "metrics", base + ".py"))
    assert callable(cells.load_reader(name))


@pytest.mark.parametrize("world", ["fenced", "queued"])
@pytest.mark.parametrize("keep", [1, 2, 3])
def test_a_trace_that_lost_calls_joins_on_those_it_kept(world, keep):
    """One execution tied by its run id anchors the count; the others
    only check it."""
    records, events, spans, links = fenced_world() if world == "fenced" \
        else queued_world()
    whole = L.join(records, trace_of(events), spans, links)
    kept = dict(sorted(links.called.items())[-keep:])
    j = L.join(records, trace_of(events), spans, L.Links(links.runs, kept))
    assert j.linked == keep < whole.linked
    assert [p.record["seq"] for p in j.pairs] \
        == [p.record["seq"] for p in whole.pairs]


def test_without_a_run_id_nothing_is_joined():
    records, events, spans, links = fenced_world()
    why = []
    assert L.join(records, trace_of(events), spans,
                  L.Links(links.runs, {}), why=why) is None
    assert L.join(records, trace_of(events), spans, L.Links([], {})) is None
    assert "run id" in why[0]


def test_a_call_names_the_run_it_enqueued_through_its_continuation():
    """The call produces (``_p``) what a continuation consumes
    (``_c``), on the calling thread or another; the enqueue inside the
    continuation says the run id."""
    calls = {11: 100.0, 12: 300.0, 13: 500.0}
    # (line, start, end, _c): on line 0 and, for call 12, on line 1
    issues = [(0, 110.0, 150.0, 11), (1, 320.0, 390.0, 12),
              (0, 520.0, 560.0, 13), (1, 700.0, 720.0, 99)]
    enqueues = [(0, 120.0, 7), (1, 350.0, 8), (0, 530.0, 9),
                (1, 710.0, 10),         # its call is not in the trace
                (0, 600.0, 11)]         # inside no continuation
    assert L._called(calls, issues, enqueues) == {7: 100.0, 8: 300.0,
                                                  9: 500.0}
