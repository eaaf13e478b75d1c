"""``benchmark/reduce/setup.py`` over hand-made set-up records: the cut
at the window's opening, harvests kept apart from the compiles they
cause, the ``params`` phase's self time, and None where there is
nothing to read (the parent of PR 54, an empty run, a ring that lost
records)."""

import types

import pytest

from benchmark import cells
from benchmark.reduce import setup

METRICS = ("setup_compiles", "setup_compile_s", "setup_compile_uncached_s",
           "setup_harvest_s", "setup_params_s")
T_START, SETUP_S = 1000.0, 50.0
T_OPEN = T_START + SETUP_S


def cause(phase=None, program=None, signature=None, part=None):
    return {"phase": phase, "program": program, "signature": signature,
            "part": part}


def compiled(seq, t1, name, trace_s, lower_s, backend_s, cache,
             why=None):
    r = {"kind": "compile", "seq": seq,
         "t0": t1 - trace_s - lower_s - backend_s, "t1": t1,
         "fun_name": name, "trace_s": trace_s, "lower_s": lower_s,
         "backend_s": backend_s, "cache": cache, "cause": why}
    if cache == "hit":
        r.update(retrieval_s=backend_s / 2, saved_s=10.0)
    return r


def records():
    """An engine's build and warm-up, then a compile in the window."""
    return [
        compiled(0, 1002.0, "jit(_normal)", 0.1, 0.2, 0.3, "none",
                 cause("params")),
        compiled(1, 1004.0, "jit(multiply)", 0.0, 0.1, 0.1, "none",
                 cause("params")),
        {"kind": "phase", "seq": 2, "t0": 1001.0, "t1": 1011.0,
         "phase": "params", "cause": None},
        compiled(3, 1012.0, "jit(broadcast_in_dim)", 0.0, 0.1, 0.2,
                 "none", cause("cache")),
        {"kind": "phase", "seq": 4, "t0": 1011.0, "t1": 1013.0,
         "phase": "cache", "cause": None},
        # a fresh signature: the side compile loads from the cache ...
        compiled(5, 1020.0, "jit(pool_step)", 1.0, 2.0, 0.5, "hit",
                 cause(None, "serve.decode", 0, "harvest")),
        {"kind": "harvest", "seq": 6, "t0": 1016.0, "t1": 1021.0,
         "program": "serve.decode", "signature": 0, "cause": None},
        # ... a prefill bucket's call compiles anew and is written ...
        compiled(7, 1030.0, "jit(paged_prefill_sample)", 0.5, 0.5, 4.0,
                 "miss", cause(None, "serve.paged_prefill", 1, "call")),
        # ... and an eager op of the warm-up loads in no time
        compiled(8, 1031.0, "jit(_threefry_split)", 0.0, 0.0, 0.25,
                 "hit"),
        # inside the window: past the cut
        compiled(9, T_OPEN + 3.0, "jit(pool_step)", 1.0, 1.0, 9.0, "miss",
                 cause(None, "serve.decode", 1, "call")),
        {"kind": "harvest", "seq": 10, "t0": T_OPEN + 1.0,
         "t1": T_OPEN + 2.0, "program": "serve.decode", "signature": 1,
         "cause": None},
    ]


def run_of(monkeypatch, recs, **kw):
    monkeypatch.setattr(setup, "setup_records", lambda: recs)
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(t_start=T_START), setup_s=SETUP_S, **kw)


def read(run, metric):
    return cells.load_reader(metric)(run)


def test_the_five_sums(monkeypatch, capsys):
    run = run_of(monkeypatch, records())
    assert read(run, "setup_compiles") == 6          # the cut leaves one
    # every compile no harvest caused: 0.6 + 0.2 + 0.3 + 5.0 + 0.25
    assert read(run, "setup_compile_s") == pytest.approx(6.35)
    # of them the backend's part where the cache did not serve:
    # 0.3 + 0.1 + 0.2 + 4.0 (the hit's 0.25 is a read)
    assert read(run, "setup_compile_uncached_s") == pytest.approx(4.6)
    # the harvest whole, its 3.5 s of compile inside it, once
    assert read(run, "setup_harvest_s") == pytest.approx(5.0)
    # the phase's 10 s less the 0.8 s its two compiles took
    assert read(run, "setup_params_s") == pytest.approx(9.2)
    # disjoint, so inside set-up
    assert 6.35 + 5.0 + 9.2 <= SETUP_S
    # one line, printed once for the five readers
    out = capsys.readouterr().out
    assert out.count("[setup_records]") == 1
    line, = [x for x in out.splitlines() if x.startswith("[setup_records]")]
    assert 'by_cache={"none": 3, "hit": 2, "miss": 1}' in line
    # the programs a warm cache should have served and did not, by name
    assert ('slow_uncached=[["jit(paged_prefill_sample)", '
            '"serve.paged_prefill#1:call", 1, 4.0]]') in line
    assert '"jit(pool_step)", "serve.decode#0:harvest", 3.5, "hit"' in line
    assert 'phases={"params": 10.0, "cache": 2.0}' in line
    assert 'harvests={"serve.decode": 5.0}' in line
    # the compile that a window's compiles_in_window counted, by name
    assert 'after_open=[["jit(pool_step)", "serve.decode#1:call"]]' in line
    assert '"params": [2, 0.8, 0.4]' in line
    # who asked for the instrumented programs' compiles, and what the
    # harvest took beside its own (5.0 less the 3.5 inside it)
    assert 'by_part={"harvest": [1, 3.5], "call": [1, 5.0]}' in line
    assert "harvest_beside_compiles_s=1.5" in line


def test_slow_compiles_the_cache_did_not_serve_are_named_once(monkeypatch,
                                                              capsys):
    """A fresh directory: fam.init's draws compile for seconds each, and
    the line names them once, with their count and seconds."""
    recs = [compiled(i, 1002.0 + i, "jit(_normal)", 0.0, 0.1, 2.0 + i,
                     "miss", cause("params")) for i in range(3)]
    recs.append(compiled(3, 1006.0, "jit(_normal)", 0.0, 0.1, 0.5, "none",
                         cause("params")))
    run = run_of(monkeypatch, recs)
    assert read(run, "setup_compile_uncached_s") == pytest.approx(9.5)
    assert 'slow_uncached=[["jit(_normal)", "params", 3, 9.0]]' \
        in capsys.readouterr().out


def test_the_cut_is_the_windows_opening(monkeypatch):
    late = run_of(monkeypatch, records())
    late.setup_s = SETUP_S + 10.0
    assert read(late, "setup_compiles") == 7
    assert read(late, "setup_harvest_s") == pytest.approx(6.0)
    early = run_of(monkeypatch, records())
    early.setup_s = 5.0                  # before the params phase closed
    assert read(early, "setup_compiles") == 2
    assert read(early, "setup_harvest_s") == 0.0
    assert read(early, "setup_params_s") is None


def test_a_trainer_has_no_params_phase(monkeypatch):
    recs = [r for r in records() if r["kind"] == "compile"][:2]
    for r in recs:
        r["cause"] = None
    run = run_of(monkeypatch, recs)
    assert read(run, "setup_compiles") == 2
    assert read(run, "setup_compile_s") == pytest.approx(0.8)
    assert read(run, "setup_harvest_s") == 0.0
    assert read(run, "setup_params_s") is None


@pytest.mark.parametrize("metric", METRICS)
def test_nothing_to_read_is_none(monkeypatch, metric):
    # the parent of PR 54 keeps no records
    assert read(run_of(monkeypatch, None), metric) is None
    # a run that kept no context (the readers' own test)
    monkeypatch.setattr(setup, "setup_records", records)
    assert read(types.SimpleNamespace(setup_s=1.0), metric) is None
    # a ring that had dropped its oldest records: a count that misses
    # some is no count
    lost = [dict(r, seq=r["seq"] + 3) for r in records()]
    assert read(run_of(monkeypatch, lost), metric) is None


def test_the_process_is_asked_where_the_program_keeps_records():
    from ray_tpu._private import telemetry

    t = telemetry.record_setup("phase", 1.0, 2.0, phase="config")
    assert t in setup.setup_records()


def test_without_the_programs_ring_the_loader_gives_none(monkeypatch):
    from ray_tpu._private import telemetry

    monkeypatch.delattr(telemetry, "setup_records")     # the parent
    assert setup.setup_records() is None
    run = types.SimpleNamespace(
        ctx=types.SimpleNamespace(t_start=T_START), setup_s=SETUP_S)
    for metric in METRICS:
        assert read(run, metric) is None
