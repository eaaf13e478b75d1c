"""Driver ``serve_closed``: the clients' requests and the order of their
sends with and without ``first_send_spread_s``, against an engine that
answers on a timer."""

import asyncio
import hashlib
import time

import jax.profiler  # noqa: F401 - a send's span imports it: not in a window
import numpy as np
import pytest

from benchmark.cells import HERE, load_json
from benchmark.drivers import serve_closed
from benchmark.traffic_gen import TrafficGenerator

OFFLINE = load_json(HERE, "traffic", "serve-offline-decode.json")
STAGGERED = load_json(HERE, "traffic", "serve-offline-staggered.json")
BIG = 2 ** 31 + 12345
#: sha256 over every request of ``closed_loop()`` (index, client, turn,
#: group, tail length, the prompt's bytes) as PR 26's tree drew them
DRAWN = {1: "3cfa7bb9e62fed77", 313: "e6162099c891c408",
         BIG: "3b9987a5981d971b"}


def _digest(rows) -> str:
    h = hashlib.sha256()
    for row in rows:
        for r in row:
            h.update(repr((r.index, r.client, r.turn, r.group,
                           r.tail_len)).encode())
            h.update(r.prompt.tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("seed", sorted(DRAWN))
def test_the_clients_requests_are_what_they_were(seed):
    """The key changes when a client first sends, never what: both
    files give the requests the offline cell's bounds were measured on."""
    for traffic in (OFFLINE, STAGGERED):
        rows = TrafficGenerator(traffic, seed, 50257).closed_loop()
        assert _digest(rows) == DRAWN[seed]


def test_the_staggered_file_is_the_offline_file_plus_the_key():
    assert "first_send_spread_s" not in OFFLINE
    rest = {k: v for k, v in STAGGERED.items()
            if k not in ("first_send_spread_s", "why")}
    assert rest == {k: v for k, v in OFFLINE.items() if k != "why"}
    assert STAGGERED["first_send_spread_s"] == 24.0


def test_first_send_offsets_come_from_the_file_alone():
    assert serve_closed.first_send_offsets(OFFLINE) == [0.0] * 32
    assert serve_closed.first_send_offsets(
        dict(OFFLINE, first_send_spread_s=0)) == [0.0] * 32
    got = serve_closed.first_send_offsets(STAGGERED)
    assert got == [c * 24.0 / 32 for c in range(32)]
    assert got[1] == 0.75 and got[-1] == 23.25


class TimerEngine:
    """Answers every prompt with `new` tokens, one each `step_s`; keeps
    the records ``Sender`` and the driver read."""

    def __init__(self, new: int = 3, step_s: float = 0.004):
        self.new, self.step_s, self.records = new, step_s, []

    def __call__(self, prompt):
        rec = {"id": len(self.records), "token_ts": [],
               "called": time.perf_counter(), "prompt": prompt}
        self.records.append(rec)
        return self._answer(rec, prompt)

    async def _answer(self, rec, prompt):
        for _ in range(self.new):
            await asyncio.sleep(self.step_s)
            rec["token_ts"].append(time.perf_counter())
        return np.concatenate([prompt, np.zeros(self.new, np.int32)])

    def trace_records(self):
        return self.records


def _window(traffic, seed, seconds=0.15, **engine):
    # the timer answers in milliseconds: turns enough for its window
    traffic = dict(traffic, turns_per_client=100)
    clients = TrafficGenerator(traffic, seed, 50257).closed_loop()
    offsets = serve_closed.first_send_offsets(traffic)
    eng = TimerEngine(**engine)

    async def main():
        w = await serve_closed.closed_window(eng, clients, offsets,
                                             seconds, 1.0)
        w.exhausted = await w.finish()
        return w

    return clients, eng, asyncio.run(main())


@pytest.mark.parametrize("seed", sorted(DRAWN))
def test_without_the_key_every_client_sends_at_once(seed):
    """Today's driver: the window opens with the load, and the first 32
    sends are the clients' first prompts in the file's order."""
    clients, eng, w = _window(OFFLINE, seed)
    assert w.t0 == w.load_t0 and w.alive and w.exhausted == 0
    first = [row[0] for row in clients]
    assert w.sender.order[:32] == [r.index for r in first]
    for rec, req in zip(eng.records, first):
        assert rec["prompt"].tobytes() == req.prompt.tobytes()
    # a client's second prompt follows its own first answer
    assert set(w.sender.order[32:64]) == {row[1].index for row in clients}
    assert eng.records[31]["called"] - w.load_t0 < 0.5


@pytest.mark.parametrize("seed", [1, BIG])
def test_with_the_key_the_clients_start_in_turn(seed):
    traffic = dict(STAGGERED, first_send_spread_s=0.32)    # 10 ms apart
    clients, eng, w = _window(traffic, seed, step_s=0.002)
    offsets = serve_closed.first_send_offsets(traffic)
    assert offsets == [c * 0.01 for c in range(32)]
    sent = w.sender.sent
    for c, row in enumerate(clients):
        # never early; late by what the loop's other tasks take
        assert offsets[c] <= sent[row[0].index] - w.load_t0 \
            < offsets[c] + 0.5
    firsts = [sent[row[0].index] for row in clients]
    assert firsts == sorted(firsts)
    # the window opens when the last client has sent: the ramp is set-up
    assert w.load_t0 + offsets[-1] <= w.t0 <= firsts[-1]
    assert firsts[-1] - w.t0 < 0.05
    # by then the early clients are turns ahead: out of step
    assert sum(t < w.t0 for t in sent.values()) > 40
    assert w.alive and w.exhausted == 0
    assert w.t_end >= w.t0 + 0.15


def test_an_engine_that_falls_silent_is_seen():
    class Silent(TimerEngine):
        async def _answer(self, rec, prompt):
            await asyncio.sleep(3600)

    clients = TrafficGenerator(OFFLINE, 1, 50257).closed_loop()

    async def main():
        w = await serve_closed.closed_window(
            Silent(), clients, [0.0] * 32, 0.05, 0.1)
        return w, await w.finish()

    w, exhausted = asyncio.run(main())
    assert not w.alive and exhausted == 0
    assert 0.15 <= w.t_end - w.t0 < 1.0
