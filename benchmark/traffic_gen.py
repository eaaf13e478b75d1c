"""One general generator of serving traffic from a data file.

Adapted from the program's ``ray_tpu/serve/traffic.py TrafficGenerator``
(seeded prefix groups, ragged user tails), with real lengths and the
model's own vocabulary.  Two things differ on purpose:

* The SHAPE of the traffic -- arrival times, and each request's prefix
  group and tail length -- comes from seeds written in the traffic file
  and is the same in every run.  ``--seed`` permutes which body meets
  which arrival (or which client; not even that where a closed-loop
  file says ``"client_lists": "file"``), and draws every token.  Runs of
  different seeds then do the same work in another order, and their
  spread is the system's, not the dice's.
* Nothing is timed here: the drivers clock requests from their due
  time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray              # int32 (len,)
    group: int                      # shared-prefix group, -1 = unshared
    tail_len: int
    due_s: Optional[float] = None   # open loop: offset from window start
    client: Optional[int] = None    # closed loop: who sends it
    turn: Optional[int] = None      # closed loop: the client's n-th


def draw_lengths(spec: Dict[str, Any], n: int,
                 rng: np.random.Generator) -> np.ndarray:
    """n lengths from ``{"dist": ...}``: ``poisson1`` is 1 + Poisson(mean)
    capped at ``max``; ``uniform`` is whole numbers lo..hi inclusive
    (one length where lo = hi)."""
    dist = spec["dist"]
    if dist == "poisson1":
        return np.minimum(1 + rng.poisson(spec["mean"], n),
                          int(spec["max"])).astype(np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["lo"]), int(spec["hi"]) + 1, n)
    raise ValueError(f"unknown length distribution {dist!r}")


def arrival_times(spec: Dict[str, Any], seconds: float) -> np.ndarray:
    """Arrival offsets inside [0, seconds): a Poisson process of
    ``rate_rps`` from the file's own ``schedule_seed``: a shorter window
    sees a prefix of the same schedule.  (The exponential gap is drawn
    as gamma(1): the draw the cells' bounds were measured on.)"""
    rate = float(spec["rate_rps"])
    rng = np.random.default_rng(int(spec["schedule_seed"]))
    out: List[float] = []
    t = 0.0
    while True:
        t += float(rng.gamma(1.0, 1.0 / rate))
        if t >= seconds:
            return np.asarray(out)
        out.append(t)


def _bodies(prompts: Dict[str, Any], n: int) -> List[tuple]:
    """(group, tail_len) for n requests from the file's ``shape_seed``."""
    rng = np.random.default_rng(int(prompts["shape_seed"]))
    groups = int(prompts.get("prefix_groups", 0))
    shared = rng.random(n) < float(prompts.get("p_shared", 0.0))
    which = rng.integers(0, max(groups, 1), n)
    tails = draw_lengths(prompts["tail"], n, rng)
    return [(int(which[i]) if groups and shared[i] else -1, int(tails[i]))
            for i in range(n)]


def _fold(seed: int) -> int:
    return int(seed) % (2 ** 32)


class TrafficGenerator:
    """Expands a traffic file's ``arrivals``/``clients`` and ``prompts``
    sections into requests for one run."""

    def __init__(self, traffic: Dict[str, Any], seed: int, vocab: int):
        self.traffic = traffic
        self.prompts = traffic["prompts"]
        self.vocab = int(vocab)
        self._rng = np.random.default_rng([_fold(seed), 0x7AFF1C])
        self.prefixes = [
            self._tokens(int(self.prompts["prefix_len"]))
            for _ in range(int(self.prompts.get("prefix_groups", 0)))]

    def _tokens(self, n: int) -> np.ndarray:
        return self._rng.integers(0, self.vocab, n).astype(np.int32)

    def prompt(self, group: int, tail_len: int) -> np.ndarray:
        tail = self._tokens(tail_len)
        if group < 0:
            return tail
        return np.concatenate([self.prefixes[group], tail])

    def open_loop(self, seconds: float) -> List[Request]:
        due = arrival_times(self.traffic["arrivals"], seconds)
        bodies = _bodies(self.prompts, len(due))
        order = self._rng.permutation(len(due))
        return [Request(index=i, prompt=self.prompt(*bodies[order[i]]),
                        group=bodies[order[i]][0],
                        tail_len=bodies[order[i]][1], due_s=float(due[i]))
                for i in range(len(due))]

    def closed_loop(self) -> List[List[Request]]:
        """Per client, the prompts it sends one after another.  Each
        list of lengths is fixed by the file; the seed decides which
        client gets which list, so every wave holds the same lengths.
        ``"client_lists": "file"`` in the traffic file gives client c
        list c under every seed: where the clients are out of step (a
        window over a piece of a cycle holds some clients' turns and not
        others'), the seed must not deal the lists, or each seed's
        window holds other prefills.  The seed still draws every token."""
        n_clients = int(self.traffic["clients"])
        turns = int(self.traffic["turns_per_client"])
        bodies = _bodies(self.prompts, n_clients * turns)
        lists = self.traffic.get("client_lists", "seed")
        if lists not in ("seed", "file"):
            raise ValueError(f"client_lists is 'seed' or 'file', not "
                             f"{lists!r}")
        order = self._rng.permutation(n_clients) if lists == "seed" \
            else range(n_clients)
        out, index = [], 0
        for c in range(n_clients):
            mine = bodies[order[c] * turns:(order[c] + 1) * turns]
            row = []
            for turn, (group, tail_len) in enumerate(mine):
                row.append(Request(index=index,
                                   prompt=self.prompt(group, tail_len),
                                   group=group, tail_len=tail_len,
                                   client=c, turn=turn))
                index += 1
            out.append(row)
        return out
