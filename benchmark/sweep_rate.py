"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 -m benchmark.sweep_rate --workload gpt2-xl.serve-chat-shared
        --rates 1.5 2.0 2.5 3.0 3.5 --seconds 40 [--seed 11]

One process and one engine; per rate one window of the cell's own
traffic with ``arrivals.rate_rps`` replaced, drained before the next.
A rate is sustained when the backlog at the window's end is no larger
than in steady state and the queue wait of the window's second half is
not above the first's: past the knee both grow all through the window.
The cell then runs at about four fifths of the knee (the number goes
into the traffic file by hand; PERF.md keeps the table).  Needs the
chip: there is no CPU path.
"""

from __future__ import annotations

import argparse
import asyncio
import copy
import os
import sys
import time

from benchmark import estimators


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=40.0)
    p.add_argument("--seed", type=int, default=11)
    args = p.parse_args(argv)

    from benchmark import harness
    from benchmark.cells import load_cell
    from benchmark.drivers.serve_open import (open_window,
                                              warmup_requests)
    from benchmark.serving import Sender, build_engine
    from benchmark.traffic_gen import TrafficGenerator
    from ray_tpu._private.compile_cache import enable_compile_cache

    cell = load_cell(args.workload)
    enable_compile_cache()
    device = harness.require_device(cell.chips)
    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=False, t_start=t_start,
                      peaks=harness.peaks_for(device["kind"]),
                      device=device, trace_dir=os.getcwd())
    engine, eng = build_engine(ctx)

    def requests_at(rate: float):
        traffic = copy.deepcopy(cell.traffic)
        traffic["arrivals"]["rate_rps"] = rate
        gen = TrafficGenerator(traffic, args.seed, engine.cfg.vocab_size)
        return gen, gen.open_loop(args.seconds)

    async def sweep():
        gen, widest = requests_at(max(args.rates))
        warm, labels = warmup_requests(gen, widest, eng)
        sender = Sender(engine)
        await asyncio.gather(*[sender.send(r) for r in warm])
        harness.say("warmup", requests=labels,
                    setup_s=round(time.perf_counter() - t_start, 1))
        for rate in args.rates:
            _, reqs = requests_at(rate)
            w = await open_window(engine, eng, reqs, args.seconds,
                                  float(cell.traffic["drain_s"]))
            t1 = w.t0 + args.seconds
            done = [r for r in w.rows if r.get("finish") is not None]
            ttft = [estimators.ttft_ms(r["first_token"], r["due"])
                    for r in w.rows if r.get("first_token")]
            gaps = [g for r in done
                    for g in estimators.token_gaps_ms(r["token_ts"])]
            wait = [(r["due"] - w.t0, (r["admit"] - r["due"]) * 1e3)
                    for r in w.rows if r.get("admit")]
            half = args.seconds / 2
            first = [x for t, x in wait if t < half] or [0.0]
            second = [x for t, x in wait if t >= half] or [0.0]
            stamps = [t for r in w.rows for t in r["token_ts"]]
            rate_out = estimators.emission_rate(stamps, w.t0, t1)
            harness.say(
                "rate", rate_rps=rate, sent=len(w.rows),
                finished=len(done), unfinished_after_drain=w.unfinished,
                in_engine_at_window_end=sum(
                    1 for r in w.rows
                    if r["sent"] <= t1 and (r.get("finish") or 1e18) > t1),
                ttft_p50_ms=round(estimators.percentile(ttft, 50), 1),
                ttft_p90_ms=round(estimators.percentile(ttft, 90), 1),
                gap_p50_ms=round(estimators.percentile(gaps, 50), 1),
                gap_p95_ms=round(estimators.percentile(gaps, 95), 1),
                wait_first_half_ms=round(sum(first) / len(first), 1),
                wait_second_half_ms=round(sum(second) / len(second), 1),
                out_tokens_per_s=round(rate_out[0], 1) if rate_out
                else None,
                drained_s=round(w.t_end - t1, 1))
        engine.shutdown_engine()

    asyncio.run(sweep())
    return 0


if __name__ == "__main__":
    sys.exit(main())
