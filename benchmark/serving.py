"""What the two serving drivers share: building the program's engine
from a cell's files, warming it up, checking answers against the
reference, and turning the engine's own records into rows.

The engine is the one a ``ray_tpu.serve`` user deploys:
``build_llm_deployment(scheduler="continuous", kv_layout="paged")``,
greedy.  It runs on this process's asyncio loop, so the load generator
and the engine share one thread: a send can only happen between two
steps of the engine, which is why requests are clocked from their due
time and the generator's lag is reported.
"""

from __future__ import annotations

import asyncio
import time
import types
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from benchmark import correct
from benchmark.harness import (Ctx, Profiler, program_overrides, say,
                               span)
from benchmark.traffic_gen import Request


def build_engine(ctx: Ctx):
    """The engine and its sizes.  ``kv_pool_bytes`` in the traffic file
    is the chip's budget for the K/V pool; the number of blocks follows
    from the configuration's bytes per token."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.serve.llm import build_llm_deployment

    config, spec = ctx.cell.config, ctx.cell.traffic["engine"]
    overrides = program_overrides(ctx.cell)
    overrides["param_dtype"] = jnp.dtype(spec["param_dtype"]).type
    block = int(spec["kv_block_size"])
    per_block = ctx.cell.family.kv_bytes_per_token(config) * block
    n_blocks = int(spec["kv_pool_bytes"]) // per_block
    t0 = time.perf_counter()
    engine = build_llm_deployment(
        config["program"]["family"], config["program"]["preset"],
        scheduler="continuous", kv_layout="paged", kv_block_size=block,
        kv_num_blocks=n_blocks, max_slots=int(spec["max_slots"]),
        max_new_tokens=int(spec["max_new_tokens"]), temperature=0.0,
        prefill_bucket=int(spec["prefill_bucket"]), seed=ctx.jax_seed,
        config_overrides=overrides).func_or_class()
    say("engine", build_seconds=round(time.perf_counter() - t0, 2),
        kv_num_blocks=n_blocks, kv_pool_bytes=n_blocks * per_block,
        max_slots=spec["max_slots"],
        max_new_tokens=spec["max_new_tokens"],
        param_dtype=str(jax.tree.leaves(engine.params)[0].dtype))
    return engine, types.SimpleNamespace(
        block=block, n_blocks=n_blocks, bucket=int(spec["prefill_bucket"]),
        max_slots=int(spec["max_slots"]),
        new_tokens=int(spec["max_new_tokens"]))


def padded(n_tail: int, bucket: int) -> int:
    """The prefill program's padded tail length for n_tail new tokens."""
    return -(-n_tail // bucket) * bucket


def next_record_id(engine) -> int:
    recs = engine.trace_records()
    return 1 + max((r["id"] for r in recs), default=-1)


class Sender:
    """Sends prompts to the engine and remembers the order, which is
    the order of the engine's record ids (its enqueue record is made
    before the call first yields)."""

    def __init__(self, engine):
        self.engine = engine
        self.first_id = next_record_id(engine)
        self.order: List[int] = []
        self.sent: Dict[int, float] = {}
        self.outs: Dict[int, Any] = {}
        self.errors: Dict[int, str] = {}

    async def send(self, req: Request) -> None:
        with span("bench.send"):
            self.order.append(req.index)
            self.sent[req.index] = time.perf_counter()
            call = self.engine(req.prompt)
        try:
            out = await call
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 - a shed or failed request
            self.errors[req.index] = repr(e)   # counts as failed
            return
        with span("bench.collect"):
            self.outs[req.index] = np.asarray(out)

    def rows(self, requests: Sequence[Request], new_tokens: int,
             due: Optional[Dict[int, float]] = None
             ) -> List[Dict[str, Any]]:
        """One row per request sent: the driver's own stamps joined to
        the engine's lifecycle record."""
        by_id = {r["id"]: r for r in self.engine.trace_records()}
        by_index = {req.index: req for req in requests}
        rows = []
        for k, index in enumerate(self.order):
            req, rec = by_index[index], by_id.get(self.first_id + k)
            out = self.outs.get(index)
            row = {"index": index, "group": req.group,
                   "prompt_len": len(req.prompt),
                   "sent": self.sent[index],
                   "due": None if due is None else due[index],
                   "error": self.errors.get(index),
                   "collected": out is not None,
                   "answered": out is not None
                   and len(out) == len(req.prompt) + new_tokens
                   and np.array_equal(out[:len(req.prompt)], req.prompt)}
            if rec is not None:
                kv = rec.get("kv_reserve")
                row.update(
                    status=rec["status"], tokens=rec["tokens"],
                    enqueue=rec["enqueue"], admit=rec["admit"],
                    first_token=rec["first_token"],
                    finish=rec["finish"],
                    token_ts=list(rec["token_ts"] or ()),
                    hit_blocks=kv[3] if kv else 0,
                    bucket=rec["bucket"])
            else:
                row.update(status="lost", tokens=0, token_ts=[])
            rows.append(row)
        return rows


def all_token_stamps(engine) -> List[float]:
    """Emission times of every token the engine has produced and still
    remembers, warm-up and requests cut by the window's end included."""
    return sorted(t for r in engine.trace_records()
                  for t in (r["token_ts"] or ()))


def last_emission(engine) -> float:
    """Host time of the newest token the engine has emitted."""
    return max((r["token_ts"][-1] for r in engine.trace_records()
                if r["token_ts"]), default=float("-inf"))


def tie_tol(cell, n_layer: int) -> float:
    """The near-tie tolerance an answer is held to: the family's own
    where it states one (``logit_tie_tol(config)``, with its reason
    beside it: a router over many near-flat logits does not round as a
    dense block does), else the one derived for dense pre-norm blocks
    in bf16, which grows with the depth the engine runs."""
    stated = getattr(cell.family, "logit_tie_tol", None)
    return float(stated(cell.config)) if stated \
        else correct.logit_tie_tol(n_layer)


def check_answer(ctx: Ctx, engine, label: str, req: Request, out,
                 hit_blocks: int, want_hit: bool, new_tokens: int
                 ) -> Dict[str, Any]:
    """One answered request against the plain reference."""
    cfg = engine.cfg
    n = len(req.prompt)
    if out is None or len(out) != n + new_tokens:
        return {"ok": False, "request": label, "reason": "not answered"}
    lg = correct.reference_generated_logits(
        ctx.cell.reference, engine.params, out, n, vocab_size=cfg.vocab_size,
        max_seq=cfg.max_seq, **ctx.cell.reference_kwargs)
    res = correct.check_greedy(lg, out[n:], tie_tol(ctx.cell, cfg.n_layer))
    res.update(request=label, prompt_len=n, hit_blocks=hit_blocks)
    res["ok"] = bool(res["ok"] and (hit_blocks > 0) == want_hit)
    return res


async def warm_up(ctx: Ctx, engine, eng, warm: Sequence[Request],
                  labels: Sequence[str], to_check, watch, split
                  ) -> List[Dict[str, Any]]:
    """Set-up's requests: send `warm` at once (the engine admits them in
    order, so a repeat meets its blocks resident), wait for every
    answer, then hold the answers named in `to_check` -- pairs of
    (label, whether it must have hit the prefix cache) -- to the
    reference.  Fills `split` with the seconds each part took."""
    t0 = time.perf_counter()
    sender = Sender(engine)
    await asyncio.gather(*[sender.send(r) for r in warm])
    rows = {r["index"]: r for r in sender.rows(warm, eng.new_tokens)}
    split["warmup_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    checks = []
    for label, want_hit in to_check:
        req = warm[labels.index(label)]
        checks.append(check_answer(
            ctx, engine, label, req, sender.outs.get(req.index),
            rows[req.index].get("hit_blocks", 0), want_hit,
            eng.new_tokens))
    split["reference_s"] = time.perf_counter() - t0
    say("warmup", compiles=watch.compiles, cache_hits=watch.hits,
        cache_writes=watch.writes,
        answered=sum(r["answered"] for r in rows.values()), of=len(rows))
    return checks


def in_flight_spans(rows: Sequence[Dict[str, Any]], t_end: float):
    """(start, end) on the host clock of every request's life in the
    engine: for telling device idle with work pending from idle with
    nothing to do."""
    return [(r["enqueue"], r["finish"] if r.get("finish") else t_end)
            for r in rows if r.get("enqueue") is not None]


async def trace_between(prof: Profiler, out, t0: float,
                        trace_at) -> None:
    """Profile from ``t0 + trace_at[0]`` to ``t0 + trace_at[1]`` and
    leave the two marks' host times on `out`.  The marks are spans of
    their own, so the reduction can cut the device events to the window
    and lay host times over the trace.  The caller reduces the file
    (`prof.reduce()`) once its window is over."""
    await asyncio.sleep(max(0.0, t0 + trace_at[0] - time.perf_counter()))
    prof.start()
    with span("bench.window_start"):
        out.trace_t0 = time.perf_counter()
    await asyncio.sleep(max(0.0, t0 + trace_at[1] - time.perf_counter()))
    with span("bench.window_end"):
        out.trace_t1 = time.perf_counter()
    prof.stop()


def trace_window(ctx: Ctx):
    """The cell's traced sub-window, or None on an untraced run; moved
    inside a window shorter than the file expects."""
    at = ctx.cell.traffic.get("trace_window_s") if ctx.trace else None
    if at and at[1] > ctx.seconds:
        at = [ctx.seconds * 0.3, ctx.seconds * 0.7]
    return at
