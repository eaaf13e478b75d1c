"""Make the sets of runs a bound is fixed from, as the driver would.

    python3 -m benchmark.measure_sets --workload <cell> [--sets 2]
        [--runs 6] [--seconds <run_seconds>] [--traced 1] [--out <dir>]
        [--seeds 5,7,...]

Runs BENCHMARK.json's command once per run, each a process of its own
(this one never touches JAX, so the chip is free for each child), the
same seeds in every set, and prints per set and metric the median and
the spread the contract defines: the distance between the first and
third quartile (``statistics.quantiles(values, n=4)``) as a share of
the median.  ``--seeds`` gives the seeds instead, ``sets x runs`` of
them dealt to the sets in order: a seed a run, where the question is
how far seeds lie apart and not whether a seed repeats.  ``--traced n``
adds ``--trace 1`` runs at the end, on the first n seeds.  Every result
line is kept in ``<out>/<cell>.jsonl``, and a serving run's ``[window]``
line (how its window was cut and what it held) is shown under its own.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

#: small and large, as the driver's are ("a little over 2**31")
SEEDS = (11, 2147483659, 313, 2147484001, 5077, 2147490013, 77, 901)


def one_run(command, workload, seed, seconds, trace, log):
    argv = list(command) + ["--workload", workload, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace",
                            str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(argv, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    log.write(f"$ {' '.join(argv)}\n{proc.stdout}\n--- stderr tail\n"
              f"{proc.stderr[-3000:]}\n")
    log.flush()
    if proc.returncode != 0:
        return {"rc": proc.returncode, "wall_s": wall,
                "stderr": proc.stderr[-800:]}
    said = proc.stdout.strip().splitlines()
    line = json.loads(said[-1])
    line.update(rc=0, wall_s=wall, seed=seed, trace=trace, window=next(
        (ln for ln in said if ln.startswith("[window]")), None))
    return line


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--runs", type=int, default=6)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--traced", type=int, default=0)
    p.add_argument("--out", default="chiprun_out/sets")
    p.add_argument("--seeds", default=None)
    args = p.parse_args(argv)
    seeds = [list(SEEDS[:args.runs])] * args.sets
    if args.seeds:
        given = [int(x) for x in args.seeds.split(",")]
        if len(given) != args.sets * args.runs:
            p.error(f"--seeds wants {args.sets * args.runs} seeds")
        seeds = [given[s * args.runs:(s + 1) * args.runs]
                 for s in range(args.sets)]
    with open("BENCHMARK.json") as f:
        bench = json.load(f)
    seconds = args.seconds or bench["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out, args.workload)
    results = []
    with open(path + ".log", "a") as log, \
            open(path + ".jsonl", "a") as keep:
        for s in range(args.sets):
            for seed in seeds[s]:
                r = one_run(bench["command"], args.workload, seed,
                            seconds, 0, log)
                r["set"] = s
                results.append(r)
                keep.write(json.dumps(r) + "\n")
                keep.flush()
                shown = {k: round(v["value"], 4)
                         for k, v in r.get("metrics", {}).items()}
                print(f"set {s} seed {seed} rc {r['rc']} "
                      f"correct {r.get('correct')} failed "
                      f"{r.get('failed')} wall {r['wall_s']:.0f}s {shown}",
                      flush=True)
                if r.get("window"):
                    print("   ", r["window"], flush=True)
                if r["rc"] != 0:
                    # a cell that does not run burns no more chip time
                    print(r.get("stderr", ""), flush=True)
                    return 1
        for seed in seeds[0][:args.traced]:
            r = one_run(bench["command"], args.workload, seed,
                        seconds, 1, log)
            keep.write(json.dumps(r) + "\n")
            print("traced", json.dumps(r), flush=True)
    ok = [r for r in results if r["rc"] == 0]
    names = sorted({k for r in ok for k in r["metrics"]})
    for name in names:
        for s in range(args.sets):
            vals = [r["metrics"][name]["value"] for r in ok
                    if r["set"] == s and name in r["metrics"]]
            if name == "setup_s":
                vals = vals[1:] if s == 0 else vals   # first run compiles
            if len(vals) >= 2:
                print(f"{name} set {s}: n={len(vals)} median="
                      f"{statistics.median(vals):.6g} spread="
                      f"{spread(vals):.5%} min={min(vals):.6g} "
                      f"max={max(vals):.6g}", flush=True)
    bad = [r for r in results if r["rc"] != 0 or not r.get("correct")]
    print(f"runs={len(results)} not_ok={len(bad)}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
