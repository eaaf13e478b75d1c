"""Driver ``serve_closed``: N clients, each sending its next prompt when
its answer is whole.  A slow system receives less load, so what is
judged is the tokens per second it completes: the tokens stamped inside
the window over the window's length (benchmark/estimators.py
emission_rate), so a stall that runs on to the window's end lowers it.
The window is cut by the clock; requests in flight then count what they
produced inside it and are not drained.  That the engine was still
alive at the cut is checked apart: the clients keep the load on past
it until the engine emits its next token (32 clients in lockstep finish
a wave together, and would otherwise all see the clock pass and leave
an idle engine), and an engine that emits nothing for ``drain_s``
seconds after the cut has fallen silent: the run is not ``correct`` and
its requests in flight count as failed.

``window_requests`` in the traffic file (``measured_window``) ends the
MEASURED window on the stamp of the n-th request finished after it
opened, where that falls inside ``--seconds``: fixed work over the time
it took, so that no seed's window holds a prefill more or less than
another's by where the clock's cut falls.  The load, the clock's cut,
the check that the engine is alive past it, ``setup_s`` and the traced
sub-window are what they are without the key; only the rate's window,
and with it the result's ``t1`` that the readers cut rows by, is
shorter.  Fewer than n finished inside ``--seconds`` (a slower program,
a machine that stood still): the window is the clock's and the
``[window]`` line says ``cut="clock"``.  The count is the file's, never
chosen from the run: "as many whole turns as fit" would drop a stall
late in the window out of it.

Set-up runs one request through each prefill shape the clients will
use, and a repeat of the first (a prefix hit), to the end of their
answers: a request cannot be cut short, so this costs one full answer's
decode time (PERF.md, Open questions: an output length per request).

``first_send_spread_s`` in the traffic file staggers the clients: client
c (in the file's order, the same for every ``--seed``) sends its first
prompt ``c / clients * first_send_spread_s`` after the load starts, and
the measured window opens when the last client has sent.  The ramp is
set-up (``setup_s`` holds it, ``[setup_split]`` names it ``ramp_s``).
Clients that start together stay together: every wave ends at once, the
next wave's prefills run back to back, and the window holds a whole
number of waves and a piece, so its rate hears a shorter decode step
only through that piece.  Spread over one wave's length, a prefill
falls between decode steps all the time and the rate is the engine's.
Absent or 0, all clients send at once, as the driver always did.
"""

from __future__ import annotations

import asyncio
import time
import types

from benchmark import correct, estimators
from benchmark.harness import Ctx, Profiler, memory_peak_bytes, say
from benchmark.serving import (Sender, all_token_stamps, build_engine,
                               in_flight_spans, last_emission, padded,
                               trace_between, trace_window, warm_up)
from benchmark.traffic_gen import Request, TrafficGenerator


def warmup_requests(gen: TrafficGenerator, clients, eng):
    """One unshared request per padded prompt length in use, then the
    longest of them again: its blocks are resident by then, so it is
    the prefix-hit case."""
    by_pad = {}
    for row in clients:
        for r in row:
            by_pad.setdefault(padded(len(r.prompt), eng.bucket),
                              r.tail_len)
    reqs, labels = [], []
    for i, (p, t) in enumerate(sorted(by_pad.items())):
        reqs.append(Request(index=-1 - i, prompt=gen.prompt(-1, t),
                            group=-1, tail_len=t))
        labels.append(f"cold_pad{p}")
    longest = reqs[-1]
    reqs.append(Request(index=-1 - len(reqs), prompt=longest.prompt,
                        group=-1, tail_len=longest.tail_len))
    labels.append("repeat_hit")
    return reqs, labels


def first_send_offsets(traffic) -> list:
    """Seconds after the load starts at which each client first sends:
    ``c / clients * first_send_spread_s`` for client c, all 0 without
    the key.  From the traffic file alone, so the same for every seed."""
    n = int(traffic["clients"])
    spread = float(traffic.get("first_send_spread_s") or 0.0)
    return [c * spread / n for c in range(n)]


async def closed_window(engine, clients, offsets, seconds: float,
                        drain_s: float, trace=None):
    """Keep `clients` sending from now on, client c first after
    ``offsets[c]``, for `seconds` from when the last of them has first
    sent (at once where every offset is 0), and on to the engine's next
    emission, at most `drain_s` longer.  `trace` is called with the
    window's start and gives the tracer's coroutine.  Leaves the engine
    running: the caller reads its records, then shuts it down and calls
    ``finish()`` of what is returned."""
    sender = Sender(engine)
    load_t0 = time.perf_counter()
    closed = asyncio.Event()
    opened = asyncio.get_running_loop().create_future()
    last = len(clients) - 1              # offsets rise with c

    async def client(c, row) -> bool:
        """True if it ran out of prompts before the engine was seen
        alive past the cut (turns_per_client is then too small)."""
        if offsets[c] > 0:
            await asyncio.sleep(max(
                0.0, load_t0 + offsets[c] - time.perf_counter()))
            if c == last:
                opened.set_result(time.perf_counter())
        for req in row:
            if closed.is_set():
                return False
            await sender.send(req)
        return not closed.is_set()

    tasks = [asyncio.ensure_future(client(c, row))
             for c, row in enumerate(clients)]
    t0 = await opened if offsets[last] > 0 else load_t0
    t1 = t0 + seconds
    tracer = asyncio.ensure_future(trace(t0)) if trace else None
    await asyncio.wait(tasks, timeout=max(0.0, t1 - time.perf_counter()))
    # run on to the engine's next emission: alive at the cut
    give_up = t1 + drain_s
    while (last_emission(engine) < t1
           and time.perf_counter() < give_up):
        await asyncio.sleep(0.02)
    alive = last_emission(engine) >= t1
    closed.set()
    t_end = time.perf_counter()
    if tracer is not None:
        await tracer

    async def finish() -> int:
        """Cancel the clients still waiting for an answer; how many
        ran out of prompts."""
        done = [t for t in tasks if t.done()]
        pending = [t for t in tasks if not t.done()]
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
        return sum(t.result() for t in done)

    return types.SimpleNamespace(sender=sender, load_t0=load_t0, t0=t0,
                                 t_end=t_end, alive=alive, finish=finish)


def measured_window(rows, stamps, t0: float, t_clock: float,
                    new_tokens: int, window_requests=None):
    """What the rate is taken over and what that window held.  Without
    `window_requests` (absent or 0) it is ``(t0, t_clock]``.  With it,
    it ends on the stamp of the n-th answer made whole after `t0` (its
    last token's), if n were by `t_clock`; else the clock's window
    stands, ``cut == "clock"``.  A request is `finished` by its
    `new_tokens` stamps, a prefill is counted where its request was
    SENT (a client sends when its last answer is whole, so the sends of
    a window are the prefills its finished requests made room for)."""
    ends = sorted(r["token_ts"][-1] for r in rows
                  if len(r.get("token_ts") or ()) >= new_tokens
                  and r["token_ts"][-1] > t0)
    n = int(window_requests or 0)
    by_requests = 0 < n <= len(ends) and ends[n - 1] <= t_clock
    t1 = ends[n - 1] if by_requests else t_clock
    sent = [r for r in rows if t0 < r["sent"] <= t1]
    return types.SimpleNamespace(
        cut="requests" if by_requests else "clock", t1=t1,
        rate=estimators.emission_rate(stamps, t0, t1),
        finished=sum(e <= t1 for e in ends), prefills=len(sent),
        prompt_tokens=sum(r["prompt_len"] for r in sent))


def run(ctx: Ctx):
    import jax

    from ray_tpu._private.compile_cache import CompileWatch

    traffic = ctx.cell.traffic
    split = {"import_s": time.perf_counter() - ctx.t_start}
    watch = CompileWatch()
    t_phase = time.perf_counter()
    engine, eng = build_engine(ctx)
    gen = TrafficGenerator(traffic, ctx.seed, engine.cfg.vocab_size)
    clients = gen.closed_loop()
    flat = [r for row in clients for r in row]
    warm, labels = warmup_requests(gen, clients, eng)
    split["engine_s"] = time.perf_counter() - t_phase
    offsets = first_send_offsets(traffic)
    say("traffic", clients=len(clients), turns=len(clients[0]),
        prompt_min=min(len(r.prompt) for r in flat),
        prompt_max=max(len(r.prompt) for r in flat), warmup=labels,
        **({"first_send_spread_s": traffic["first_send_spread_s"]}
           if any(offsets) else {}))
    trace_at = trace_window(ctx)
    out = types.SimpleNamespace(trace=None)
    prof = Profiler(ctx)
    if trace_at:
        prof.prime()

    async def main():
        checks = await warm_up(
            ctx, engine, eng, warm, labels,
            [(labels[-2], False), ("repeat_hit", True)], watch, split)

        compiles_before = watch.compiles
        w = await closed_window(
            engine, clients, offsets, ctx.seconds,
            float(traffic["drain_s"]),
            (lambda t0: trace_between(prof, out, t0, trace_at))
            if trace_at else None)
        if w.t0 > w.load_t0:
            split["ramp_s"] = w.t0 - w.load_t0
        compiles_in_window = watch.compiles - compiles_before
        rows = w.sender.rows(flat, eng.new_tokens)
        stamps = all_token_stamps(engine)
        engine.shutdown_engine()
        exhausted = await w.finish()
        return types.SimpleNamespace(
            setup_s=w.t0 - ctx.t_start, t0=w.t0, t_end=w.t_end, rows=rows,
            checks=checks, compiles_in_window=compiles_in_window,
            stamps=stamps, exhausted=exhausted, alive=w.alive)

    r = asyncio.run(main())
    if trace_at:
        out.trace = prof.reduce()
    # a request whose answer the client holds, or that failed; the
    # ones in flight at the cut are cut, not failed -- unless the
    # engine never emitted again: then it fell silent with them inside
    t_clock = r.t0 + ctx.seconds
    held = measured_window(r.rows, r.stamps, r.t0, t_clock,
                           eng.new_tokens, traffic.get("window_requests"))
    rate, t1 = held.rate, held.t1
    finished = [x for x in r.rows if x["collected"] or x["error"]]
    failed = correct.count_failed(finished, eng.new_tokens)
    if not r.alive:
        failed += len(r.rows) - len(finished)
    for c in r.checks:
        say("correct", **c)
    say("window", sent=len(r.rows), finished=len(finished), failed=failed,
        clients_out_of_prompts=r.exhausted,
        compiles_in_window=r.compiles_in_window,
        tokens_in_window=rate and rate[1],
        next_emission_after_cut_s=round(
            min((t for t in r.stamps if t >= t_clock), default=t_clock)
            - t_clock, 4),
        ran_on_s=round(r.t_end - t_clock, 3), alive_at_cut=r.alive,
        cut=held.cut, window_s=round(t1 - r.t0, 4),
        finished_in_window=held.finished,
        prefills_in_window=held.prefills,
        prompt_tokens_in_window=held.prompt_tokens)
    say("setup_split", **{k: round(v, 3) for k, v in split.items()},
        setup_s=round(r.setup_s, 3))
    return types.SimpleNamespace(
        ctx=ctx, setup_s=r.setup_s,
        correct=all(c["ok"] for c in r.checks) and failed == 0
        and r.exhausted == 0 and len(finished) > 0 and r.alive,
        attempted=len(r.rows), failed=failed, rows=r.rows, t0=r.t0,
        t1=t1, stamps=r.stamps, engine=eng,
        compiles_in_window=r.compiles_in_window, trace=out.trace,
        trace_t0=getattr(out, "trace_t0", None),
        trace_t1=getattr(out, "trace_t1", None),
        in_flight=in_flight_spans(r.rows, r.t_end),
        memory_peak_bytes=memory_peak_bytes(jax.devices()[:1]))
