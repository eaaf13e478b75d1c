"""Driver ``serve_open``: an open loop at a rate fixed in the traffic
file.  Each request is sent when it is due, whether or not earlier ones
have finished, and clocked from its due time.  Requests due inside the
window are measured; after the window they are drained, and one not
finished ``drain_s`` later is failed.

Set-up makes every shared prefix resident (one cold request per group)
and runs one request through each prefill shape the window will use, so
the window starts in the steady state of a chat product and compiles
nothing.
"""

from __future__ import annotations

import asyncio
import time
import types

from benchmark import correct, estimators
from benchmark.harness import Ctx, Profiler, memory_peak_bytes, say
from benchmark.serving import (Sender, build_engine, in_flight_spans,
                               padded, trace_between, trace_window,
                               warm_up)
from benchmark.traffic_gen import Request, TrafficGenerator


def warmup_requests(gen: TrafficGenerator, measured, eng):
    """(requests, labels): per group one cold request that makes its
    prefix resident, then a hit on group 0, then one unshared request
    per padded tail length the measured requests will need."""
    tails = sorted({r.tail_len for r in measured})
    by_pad = {}
    for t in tails:
        by_pad.setdefault(padded(t, eng.bucket), t)
    typical = tails[len(tails) // 2]
    plan = [(g, typical, f"cold_prefix_g{g}")
            for g in range(len(gen.prefixes))]
    if gen.prefixes:
        plan.append((0, typical, "prefix_hit"))
    plan += [(-1, t, f"unshared_pad{p}") for p, t in sorted(by_pad.items())]
    reqs = [Request(index=-1 - i, prompt=gen.prompt(g, t), group=g,
                    tail_len=t) for i, (g, t, _) in enumerate(plan)]
    return reqs, [label for _, _, label in plan]


async def open_window(engine, eng, requests, seconds: float,
                      drain_s: float, tracer=None):
    """Send `requests` on their due times from now, wait for them up to
    `drain_s` past the window, and join the engine's records to them.
    The window starts at the returned ``t0``."""
    sender = Sender(engine)
    t0 = time.perf_counter()
    due = {r.index: t0 + r.due_s for r in requests}

    async def one(req: Request):
        delay = due[req.index] - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        await sender.send(req)

    tasks = [asyncio.ensure_future(one(r)) for r in requests]
    if tracer is not None:
        tasks.append(asyncio.ensure_future(tracer(t0)))
    done, pending = await asyncio.wait(tasks, timeout=seconds + drain_s)
    t_end = time.perf_counter()
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        t.result()
    return types.SimpleNamespace(
        t0=t0, t_end=t_end, unfinished=len(pending),
        rows=sender.rows(requests, eng.new_tokens, due))


def run(ctx: Ctx):
    import jax

    from ray_tpu._private.compile_cache import CompileWatch

    traffic = ctx.cell.traffic
    split = {"import_s": time.perf_counter() - ctx.t_start}
    watch = CompileWatch()
    t_phase = time.perf_counter()
    engine, eng = build_engine(ctx)
    gen = TrafficGenerator(traffic, ctx.seed, engine.cfg.vocab_size)
    requests = gen.open_loop(ctx.seconds)
    warm, labels = warmup_requests(gen, requests, eng)
    split["engine_s"] = time.perf_counter() - t_phase
    say("traffic", requests=len(requests),
        rate_rps=traffic["arrivals"]["rate_rps"],
        shared=sum(r.group >= 0 for r in requests),
        tail_min=min(r.tail_len for r in requests),
        tail_max=max(r.tail_len for r in requests),
        warmup=labels)
    trace_at = trace_window(ctx)
    out = types.SimpleNamespace(trace=None)
    prof = Profiler(ctx)
    if trace_at:
        prof.prime()

    async def main():
        to_check = [(labels[-1], False)]
        if "prefix_hit" in labels:
            to_check.insert(0, ("prefix_hit", True))
        checks = await warm_up(ctx, engine, eng, warm, labels, to_check,
                               watch, split)

        compiles_before = watch.compiles
        w = await open_window(
            engine, eng, requests, ctx.seconds, float(traffic["drain_s"]),
            tracer=(lambda t0: trace_between(prof, out, t0, trace_at))
            if trace_at else None)
        compiles_in_window = watch.compiles - compiles_before
        stats = engine.engine_stats()
        engine.shutdown_engine()
        return types.SimpleNamespace(
            setup_s=w.t0 - ctx.t_start, t0=w.t0, t_end=w.t_end,
            rows=w.rows, checks=checks,
            compiles_in_window=compiles_in_window,
            unfinished=w.unfinished,
            kv=stats["kv_cache"])

    r = asyncio.run(main())
    if trace_at:
        out.trace = prof.reduce()
    failed = correct.count_failed(r.rows, eng.new_tokens) \
        + (len(requests) - len(r.rows))
    for c in r.checks:
        say("correct", **c)
    late = [(x["sent"] - x["due"]) * 1e3 for x in r.rows]
    say("window", sent=len(r.rows), failed=failed,
        unfinished_tasks=r.unfinished,
        drained_s=round(r.t_end - r.t0 - ctx.seconds, 2),
        lateness_ms_max=round(max(late), 2) if late else None,
        compiles_in_window=r.compiles_in_window, kv_cache=r.kv)
    ttft = [estimators.ttft_ms(x["first_token"], x["due"])
            for x in r.rows if x.get("first_token")]
    gaps = [g for x in r.rows
            for g in estimators.token_gaps_ms(x["token_ts"])]
    pct = estimators.percentile
    say("latency", requests=len(ttft), gaps=len(gaps),
        **{f"ttft_p{q}_ms": round(pct(ttft, q), 2)
           for q in (50, 90, 99, 100) if ttft},
        **{f"gap_p{q}_ms": round(pct(gaps, q), 2)
           for q in (50, 95, 99, 100) if gaps})
    say("setup_split", **{k: round(v, 3) for k, v in split.items()},
        setup_s=round(r.setup_s, 3))
    return types.SimpleNamespace(
        ctx=ctx, setup_s=r.setup_s,
        correct=bool(r.checks) and all(c["ok"] for c in r.checks)
        and failed == 0, attempted=len(requests), failed=failed,
        rows=r.rows, t0=r.t0, t1=r.t0 + ctx.seconds,
        engine=eng, compiles_in_window=r.compiles_in_window,
        trace=out.trace, trace_t0=getattr(out, "trace_t0", None),
        trace_t1=getattr(out, "trace_t1", None),
        in_flight=in_flight_spans(r.rows, r.t_end),
        memory_peak_bytes=memory_peak_bytes(jax.devices()[:1]))
