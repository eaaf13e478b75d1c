"""The benchmark's one command.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json on the TPU it is started on and prints,
as the last line of stdout, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``.  Everything else worth reading -- step times, the set-up
split, lengths drawn, lateness -- is on earlier lines.  Without a TPU,
with fewer chips than the cell asks for, or with a ``device_kind`` that
``peaks.json`` does not know, it exits non-zero and prints no result:
there is no CPU path (``benchmark/rehearse.py`` walks the control flow
on the CPU and prints no metric).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()     # setup_s counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def result_line(run, cell, traced: bool, device: Dict[str, Any]
                ) -> Dict[str, Any]:
    """The contract's line from one run's records."""
    from benchmark.cells import load_reader
    from benchmark.reduce import xplane

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=int(run.memory_peak_bytes))
    line = {"correct": bool(run.correct), "attempted": int(run.attempted),
            "failed": int(run.failed), "metrics": metrics,
            "device": device}
    trace = getattr(run, "trace", None)
    if traced and trace is not None:
        device["busy_s"] = xplane.busy_s(trace)
        device["window_s"] = trace.window_s
        line["breakdown"] = {"device_ops": xplane.top_ops(trace, 10),
                             "idle_gaps": xplane.idle_gaps(
                                 _with_request_spans(run, trace), 10)}
    return line


def _with_request_spans(run, trace):
    """For a serving run, lay "a request was in the engine" over the
    trace as a host span, so that device idle with work pending reads
    apart from idle with nothing to do."""
    import dataclasses

    from benchmark.reduce import xplane

    t_mark = getattr(run, "trace_t0", None)
    if t_mark is None or not getattr(run, "in_flight", None):
        return trace
    to_ns = lambda t: trace.t0_ns + (t - t_mark) * 1e9  # noqa: E731
    busy = xplane.union((to_ns(a), to_ns(b)) for a, b in run.in_flight)
    window = [(trace.t0_ns, trace.t1_ns)]
    spans = [("bench.requests_in_engine", s, e - s) for s, e in busy] \
        + [("bench.no_request", s, e - s)
           for s, e in xplane.subtract(window, busy)]
    return dataclasses.replace(
        trace, host_spans=sorted(trace.host_spans + spans,
                                 key=lambda e: e[1]))


def run_cell(cell, args: argparse.Namespace, device: Dict[str, Any],
             peaks: Dict[str, float]) -> Dict[str, Any]:
    """One run of `cell` on `device`; the contract's line."""
    from benchmark import harness
    from benchmark.cells import load_driver
    from ray_tpu._private.compile_cache import enable_compile_cache

    # the program's own fixed path: JAX_COMPILATION_CACHE_DIR if set,
    # else .jax_cache/ in this checkout; no frames in locations and the
    # names in the cache's key, so the named scopes reach the trace
    cache_dir = enable_compile_cache()
    harness.say("run", workload=cell.name, seed=args.seed,
                seconds=args.seconds, trace=args.trace, device=device,
                compile_cache=cache_dir)
    ctx = harness.Ctx(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, peaks=peaks,
        device=device,
        trace_dir=os.path.join(os.getcwd(), "benchmark_out", "trace",
                               f"{cell.name}.{args.seed}"))
    run = load_driver(cell.traffic["driver"]).run(ctx)
    return result_line(run, cell, bool(args.trace), device)


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark.cells import load_cell

    cell = load_cell(args.workload)
    import ray_tpu  # noqa: F401 - the system under test must be here
    from benchmark import harness

    device = harness.require_device(cell.chips, "tpu")
    line = run_cell(cell, args, device, harness.peaks_for(device["kind"]))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
