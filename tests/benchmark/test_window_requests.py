"""The two keys a closed-loop traffic file may state (PR 38):
``client_lists`` (whose list of prompt lengths a client gets) and
``window_requests`` (the measured window ends on the n-th finished
request).  Absent, the generator and the driver are what they were;
``serve-offline-codegen.json`` alone states them."""

import asyncio
import dataclasses
import glob
import json
import os
import time

import jax.profiler  # noqa: F401 - a send's span imports it: not in a window
import numpy as np
import pytest

from benchmark import estimators, rehearse
from benchmark.cells import HERE, load_json
from benchmark.drivers import serve_closed
from benchmark.serving import all_token_stamps
from benchmark.traffic_gen import TrafficGenerator, _bodies
from tests.benchmark.test_second_family import (  # noqa: F401 - a fixture
    SERVE, TREE, merged_bench, on_the_cpu)
from tests.benchmark.test_serve_closed import (BIG, DRAWN, OFFLINE,
                                               STAGGERED, TimerEngine,
                                               _digest)

CODEGEN = load_json(HERE, "traffic", "serve-offline-codegen.json")
SEEDS = (1, 313, BIG)
KEYS = ("client_lists", "window_requests")


def _lengths(rows):
    return [[(r.turn, r.tail_len) for r in row] for row in rows]


# ------------------------------------------------------- client_lists

@pytest.fixture(scope="module")
def codegen_rows():
    return {seed: TrafficGenerator(CODEGEN, seed, 20480).closed_loop()
            for seed in SEEDS}


@pytest.mark.parametrize("seed", SEEDS)
def test_by_the_file_client_c_gets_list_c_under_every_seed(
        seed, codegen_rows):
    rows = codegen_rows[seed]
    turns = CODEGEN["turns_per_client"]
    bodies = _bodies(CODEGEN["prompts"], CODEGEN["clients"] * turns)
    assert len(rows) == 64 and all(len(row) == turns for row in rows)
    for c, row in enumerate(rows):
        assert [(r.group, r.tail_len) for r in row] == \
            bodies[c * turns:(c + 1) * turns]
        assert [(r.client, r.turn) for r in row] == \
            [(c, t) for t in range(turns)]
        assert all(len(r.prompt) == r.tail_len for r in row)
    assert _lengths(rows) == _lengths(codegen_rows[SEEDS[0]])
    assert [r.index for row in rows for r in row] == list(range(64 * turns))


@pytest.mark.parametrize("a,b", [(1, 313), (313, BIG), (1, BIG)])
def test_the_seed_still_draws_every_token(a, b, codegen_rows):
    """Same schedule of lengths, other prompts: the experts a wave
    touches, the answers and ``correct`` still differ by the seed."""
    for x, y in zip(codegen_rows[a], codegen_rows[b]):
        for p, q in zip(x, y):
            assert len(p.prompt) == len(q.prompt)
            assert not np.array_equal(p.prompt[:64], q.prompt[:64])
    again = TrafficGenerator(CODEGEN, a, 20480).closed_loop()
    assert _digest(again) == _digest(codegen_rows[a])


@pytest.mark.parametrize("seed", SEEDS)
def test_by_the_seed_the_same_lists_meet_other_clients(seed, codegen_rows):
    """Without the key the file's lists are dealt by the seed, as ever:
    the same 64 lists, another client each."""
    dealt = TrafficGenerator(
        {k: v for k, v in CODEGEN.items() if k != "client_lists"},
        seed, 20480).closed_loop()
    fixed = _lengths(codegen_rows[seed])
    assert sorted(_lengths(dealt)) == sorted(fixed)
    assert _lengths(dealt) != fixed


@pytest.mark.parametrize("seed", sorted(DRAWN))
@pytest.mark.parametrize("traffic", [OFFLINE, STAGGERED],
                         ids=["offline", "staggered"])
def test_seed_spelled_out_is_the_key_absent(traffic, seed):
    """``"client_lists": "seed"`` draws what a file without the key
    draws: the requests the offline cells' bounds were measured on."""
    rows = TrafficGenerator(dict(traffic, client_lists="seed"), seed,
                            50257).closed_loop()
    assert _digest(rows) == DRAWN[seed]
    fixed = TrafficGenerator(dict(traffic, client_lists="file"), seed,
                             50257).closed_loop()
    assert _digest(fixed) != DRAWN[seed]
    assert sorted(_lengths(fixed)) == sorted(_lengths(rows))


def test_another_word_for_client_lists_is_refused():
    with pytest.raises(ValueError, match="client_lists"):
        TrafficGenerator(dict(OFFLINE, client_lists="shuffled"), 1,
                         50257).closed_loop()


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(HERE, "traffic", "*.json"))),
    ids=lambda p: os.path.basename(p)[:-5])
def test_only_the_codegen_file_states_the_keys(path):
    """The six other cells' traffic, code paths and readings are what
    they were: the keys absent is yesterday's behaviour."""
    traffic = load_json(path)
    if os.path.basename(path) == "serve-offline-codegen.json":
        assert traffic["client_lists"] == "file"
        assert traffic["window_requests"] == 128 == 2 * traffic["clients"]
        assert traffic["driver"] == "serve_closed"
    else:
        assert not set(KEYS) & set(traffic)


# ---------------------------------------------------- measured_window

def _row(sent, ends_at, new=4, step=1.0, prompt_len=10):
    """A request sent at `sent` whose `new` tokens end at `ends_at`."""
    return {"sent": sent, "prompt_len": prompt_len,
            "token_ts": [ends_at - step * k for k in range(new)][::-1]}


def _synthetic(shift_after=None, shift=0.0, n=12):
    """Request i is sent at i and ends at i + 4; from `shift_after` on
    every stamp is `shift` later (a stall of that length)."""
    rows = [_row(float(i), float(i) + 4.0, prompt_len=10 + i)
            for i in range(n)]
    if shift_after is not None:
        for r in rows:
            r["token_ts"] = [t + shift if t >= shift_after else t
                             for t in r["token_ts"]]
            if r["sent"] >= shift_after:
                r["sent"] += shift
    return rows, sorted(t for r in rows for t in r["token_ts"])


def test_without_the_key_the_window_is_the_clocks():
    rows, stamps = _synthetic()
    for absent in (None, 0):
        w = serve_closed.measured_window(rows, stamps, 2.5, 9.5, 4, absent)
        assert (w.cut, w.t1) == ("clock", 9.5)
        assert w.rate == estimators.emission_rate(stamps, 2.5, 9.5)
        # answers whole at 4..9 after 2.5: those of requests 0..5
        assert w.finished == 6
        assert w.prefills == 7                       # sent at 3..9
        assert w.prompt_tokens == sum(10 + i for i in range(3, 10))


def test_the_window_ends_on_the_nth_finished_request():
    rows, stamps = _synthetic()
    w = serve_closed.measured_window(rows, stamps, 2.5, 9.5, 4, 3)
    # finished after 2.5: at 4, 5, 6 -> the third's last token
    assert (w.cut, w.t1, w.finished) == ("requests", 6.0, 3)
    rate, tokens, span = w.rate
    assert span == 3.5 and tokens == sum(2.5 < t <= 6.0 for t in stamps)
    assert rate == tokens / 3.5
    assert w.prefills == 4 and w.prompt_tokens == 13 + 14 + 15 + 16
    # an answer whole before the window opened is not one of the n
    assert serve_closed.measured_window(
        rows, stamps, 4.0, 9.5, 4, 1).t1 == 5.0


@pytest.mark.parametrize("n,t_clock", [(7, 9.5), (6, 8.99), (13, 99.0)])
def test_fewer_than_n_by_the_clocks_cut_fall_back_to_the_clock(n, t_clock):
    rows, stamps = _synthetic()
    w = serve_closed.measured_window(rows, stamps, 2.5, t_clock, 4, n)
    assert (w.cut, w.t1) == ("clock", t_clock)
    assert w.rate == estimators.emission_rate(stamps, 2.5, t_clock)
    # the n-th exactly on the cut is inside it
    on_it = serve_closed.measured_window(rows, stamps, 2.5, 9.0, 4, 6)
    assert (on_it.cut, on_it.t1) == ("requests", 9.0)


def test_a_request_cut_short_is_not_finished():
    rows, stamps = _synthetic()
    rows[1]["token_ts"] = rows[1]["token_ts"][:3]    # one token short
    w = serve_closed.measured_window(rows, stamps, 2.5, 9.5, 4, 3)
    assert w.t1 == 7.0                               # 4, (5 is cut), 6, 7


def test_answers_whole_in_one_wave_share_the_cut():
    """A wave's tokens carry one stamp: the n-th finish takes its whole
    wave into the window, and every answer whole by then is counted."""
    rows, _ = _synthetic()
    rows.append(_row(2.0, 6.0))                      # ends with request 2
    stamps = sorted(t for r in rows for t in r["token_ts"])
    w = serve_closed.measured_window(rows, stamps, 2.5, 9.5, 4, 3)
    assert (w.cut, w.t1, w.finished) == ("requests", 6.0, 4)
    assert w.rate[1] == sum(2.5 < t <= 6.0 for t in stamps)


def test_a_stall_before_the_nth_finish_lengthens_the_window():
    base = serve_closed.measured_window(*_synthetic(), 2.5, 20.0, 4, 5)
    rows, stamps = _synthetic(shift_after=5.5, shift=2.0)
    stalled = serve_closed.measured_window(rows, stamps, 2.5, 20.0, 4, 5)
    assert base.cut == stalled.cut == "requests"
    assert stalled.t1 == base.t1 + 2.0
    assert stalled.rate[1] == base.rate[1]           # the same work
    assert stalled.rate[0] == pytest.approx(
        base.rate[0] * 5.5 / 7.5)                    # over more seconds
    assert (stalled.finished, stalled.prefills, stalled.prompt_tokens) == \
        (base.finished, base.prefills, base.prompt_tokens)


def test_a_stall_after_it_leaves_the_rate():
    base = serve_closed.measured_window(*_synthetic(), 2.5, 20.0, 4, 5)
    rows, stamps = _synthetic(shift_after=8.5, shift=2.0)
    after = serve_closed.measured_window(rows, stamps, 2.5, 20.0, 4, 5)
    assert (after.cut, after.t1, after.rate) == \
        (base.cut, base.t1, base.rate)
    # the clock's window hears it
    assert serve_closed.measured_window(
        rows, stamps, 2.5, 12.0, 4).rate[0] < \
        serve_closed.measured_window(*_synthetic(), 2.5, 12.0, 4).rate[0]


# --------------------------------------------- against the timer engine

class RecordingEngine(TimerEngine):
    """The timer engine with the lifecycle fields ``Sender.rows`` joins,
    and an optional stall: from `stall_at` seconds after its first call
    no token leaves for `stall_s` seconds."""

    def __init__(self, stall_at=None, stall_s=0.0, **kw):
        super().__init__(**kw)
        self.stall_at, self.stall_s, self.born = stall_at, stall_s, None

    def __call__(self, prompt):
        now = time.perf_counter()
        if self.born is None:
            self.born = now
        call = super().__call__(prompt)
        self.records[-1].update(
            status="finished", tokens=0, enqueue=now, admit=now,
            first_token=None, finish=None, bucket=0)
        return call

    async def _answer(self, rec, prompt):
        for _ in range(self.new):
            await asyncio.sleep(self.step_s)
            if self.stall_at is not None:
                t = time.perf_counter() - self.born
                if self.stall_at <= t < self.stall_at + self.stall_s:
                    await asyncio.sleep(self.stall_at + self.stall_s - t)
            rec["token_ts"].append(time.perf_counter())
            rec["tokens"] += 1
        rec["first_token"], rec["finish"] = rec["token_ts"][0], \
            rec["token_ts"][-1]
        return np.concatenate([prompt, np.zeros(self.new, np.int32)])


def _timed(n, seconds, drain_s=1.0, traffic=OFFLINE, **engine):
    """One window of `traffic`'s clients against the timer; the window
    the driver would measure with ``window_requests`` n."""
    traffic = dict(traffic, turns_per_client=400)
    clients = TrafficGenerator(traffic, 1, 50257).closed_loop()
    flat = [r for row in clients for r in row]
    eng = RecordingEngine(**engine)

    async def main():
        w = await serve_closed.closed_window(
            eng, clients, serve_closed.first_send_offsets(traffic),
            seconds, drain_s)
        rows = w.sender.rows(flat, eng.new)
        stamps = all_token_stamps(eng)
        w.exhausted = await w.finish()
        return w, rows, stamps

    w, rows, stamps = asyncio.run(main())
    held = serve_closed.measured_window(rows, stamps, w.t0,
                                        w.t0 + seconds, eng.new, n)
    return w, rows, stamps, held


def test_against_the_timer_the_window_ends_on_the_nth_finish():
    # 32 clients, 3 tokens 4 ms apart: a wave of answers each ~12 ms
    w, rows, stamps, held = _timed(64, 1.0)
    assert w.alive and w.exhausted == 0
    assert held.cut == "requests" and held.finished >= 64
    ends = sorted(r["token_ts"][-1] for r in rows
                  if len(r["token_ts"]) == 3 and r["token_ts"][-1] > w.t0)
    assert held.t1 == ends[63] < w.t0 + 1.0
    assert held.finished == sum(e <= held.t1 for e in ends)
    assert held.rate == estimators.emission_rate(stamps, w.t0, held.t1)
    assert held.rate[1] >= 3 * 64 and held.rate[2] == held.t1 - w.t0
    # the load stayed on to the clock's cut and past it
    assert w.t_end >= w.t0 + 1.0 > held.t1
    assert max(r["sent"] for r in rows) > held.t1
    assert held.prefills == sum(w.t0 < r["sent"] <= held.t1 for r in rows)
    assert held.prompt_tokens == sum(
        r["prompt_len"] for r in rows if w.t0 < r["sent"] <= held.t1)
    # the same records under the clock alone: a longer window
    clock = serve_closed.measured_window(rows, stamps, w.t0, w.t0 + 1.0, 3)
    assert clock.cut == "clock" and clock.finished > held.finished


def test_against_the_timer_a_stall_before_the_nth_lowers_the_rate():
    _, _, _, base = _timed(64, 2.0)
    _, _, _, stalled = _timed(64, 2.0, stall_at=0.02, stall_s=0.8)
    assert base.cut == stalled.cut == "requests"
    assert stalled.rate[2] > base.rate[2] + 0.5      # the window grew
    assert stalled.rate[0] < base.rate[0]            # and the rate fell


def test_against_the_timer_a_stall_after_the_nth_is_still_seen():
    """The rate's window is over, the run's is not: an engine that falls
    silent before the clock's cut and stays so fails `alive`, and the
    driver then counts the run not correct, whatever the rate."""
    w, rows, stamps, held = _timed(64, 1.0, drain_s=0.2, stall_at=0.6,
                                   stall_s=30.0)
    assert held.cut == "requests" and held.t1 < w.t0 + 0.6
    assert held.rate == estimators.emission_rate(stamps, w.t0, held.t1)
    assert not w.alive
    assert 1.2 <= w.t_end - w.t0 < 2.5
    # nothing was stamped between the stall's start and the give-up
    assert max(stamps) < w.t0 + 0.7


def test_against_the_timer_fewer_than_n_fall_back_to_the_clock():
    w, rows, stamps, held = _timed(10 ** 6, 0.2)
    assert w.alive
    assert held.cut == "clock" and held.t1 == w.t0 + 0.2
    assert held.rate == estimators.emission_rate(stamps, w.t0, w.t0 + 0.2)
    assert 0 < held.finished < 10 ** 6


def test_against_the_timer_staggered_clients_open_the_window_late():
    """With ``first_send_spread_s`` the n are counted from when the last
    client has sent, not from the load's start."""
    w, rows, _, held = _timed(
        40, 1.0, traffic=dict(STAGGERED, first_send_spread_s=0.32),
        step_s=0.002)
    assert w.t0 > w.load_t0 + 0.3
    early = [r for r in rows if r["token_ts"]
             and r["token_ts"][-1] <= w.t0]
    assert len(early) > 20                   # whole before it opened
    assert held.cut == "requests" and held.t1 > w.t0
    assert held.finished >= 40


# ------------------------------------------------- through the command

@pytest.mark.parametrize("n,cut", [(8, "requests"), (10 ** 6, "clock")])
def test_a_closed_loop_file_with_both_keys_walks_the_command(
        n, cut, on_the_cpu, capsys):
    """The second family's closed-loop file plus the two keys, through
    ``run.run_cell`` on the CPU: the ``[window]`` line says how the
    window was cut and what it held, and the result's rate is that
    window's."""
    cell = rehearse.tiny_cell(SERVE, merged_bench(), TREE)
    assert not set(KEYS) & set(cell.traffic)
    cell = dataclasses.replace(cell, traffic=dict(
        cell.traffic, client_lists="file", window_requests=n))
    line = json.loads(json.dumps(rehearse.walk(cell, 0)))
    said = capsys.readouterr().out
    assert line["correct"] is True and line["failed"] == 0, said
    window = [ln for ln in said.splitlines() if ln.startswith("[window]")]
    assert len(window) == 1
    facts = dict(kv.split("=", 1) for kv in window[0].split()[1:])
    assert json.loads(facts["cut"]) == cut
    for key in ("window_s", "finished_in_window", "prefills_in_window",
                "prompt_tokens_in_window", "tokens_in_window"):
        assert float(facts[key]) > 0, window
    seconds = float(rehearse.SECONDS)
    if cut == "requests":
        assert int(facts["finished_in_window"]) >= n
        assert float(facts["window_s"]) < seconds
    else:
        assert float(facts["window_s"]) == seconds
    assert line["metrics"]["serve_out_tokens_per_s"]["value"] == \
        pytest.approx(int(facts["tokens_in_window"])
                      / float(facts["window_s"]), rel=0.02)  # as printed
