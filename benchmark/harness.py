"""What every driver shares: the run's context, the device check, the
profiler switch and the lines printed before the result."""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict

from benchmark.cells import HERE, Cell, load_json


def say(tag: str, **facts: Any) -> None:
    """One readable line per group of facts, on stdout before the
    result line (which is always the last)."""
    print(f"[{tag}] " + " ".join(
        f"{k}={json.dumps(v, default=str)}" for k, v in facts.items()),
        flush=True)


@dataclasses.dataclass
class Ctx:
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    #: perf_counter() when the process started to run the benchmark
    t_start: float
    peaks: Dict[str, float]
    #: as require_device gives it
    device: Dict[str, Any]
    trace_dir: str

    @property
    def jax_seed(self) -> int:
        """--seed may be a little over 2**31; a PRNG key and the
        engine's ``seed + 1`` want it inside int32."""
        return int(self.seed) % (2 ** 31 - 2)


def require_device(chips: int, platform: str = "tpu") -> Dict[str, Any]:
    """The device as JAX reports it, or SystemExit where JAX finds no
    accelerator or fewer chips than the cell asks for.  There is no CPU
    fallback."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != platform:
        raise SystemExit(
            f"benchmark: JAX found platform {device['platform']!r}, not "
            f"{platform!r}: nothing to measure")
    if device["count"] < chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chips, JAX found "
            f"{device['count']}")
    return device


def program_overrides(cell: Cell) -> Dict[str, Any]:
    """What the program's configuration is built from: the published
    sizes as the cell's family maps them, then the traffic file's
    ``config_overrides`` (remat policy, kernels), with a data type given
    by its name."""
    import jax.numpy as jnp

    extra = dict(cell.traffic.get("config_overrides") or {})
    for key, value in extra.items():
        if key.endswith("dtype") and isinstance(value, str):
            extra[key] = jnp.dtype(value).type
    return {**cell.family.sizes(cell.config), **extra}


def peaks_for(kind: str) -> Dict[str, float]:
    table = load_json(HERE, "peaks.json")
    if kind.startswith("_") or kind not in table:
        raise SystemExit(
            f"benchmark: device_kind {kind!r} is not in peaks.json; add "
            "its published peaks with their source, never a default")
    return table[kind]


def memory_peak_bytes(devices, program_peak: int = 0) -> int:
    """Peak bytes on the fullest chip.  The allocator's
    ``peak_bytes_in_use`` does not see a program's temporaries on this
    backend (PERF.md, PR 22), so where the driver knows the compiled
    peak of the program it ran, the larger of the two stands."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return max(peak, int(program_peak))


class Profiler:
    """``jax.profiler`` around a short window, Python tracing off (it
    alone wrote 300,000 events in two seconds of serving)."""

    def __init__(self, ctx: "Ctx"):
        self.log_dir = ctx.trace_dir
        #: off the chip there is no device plane to reduce
        self.required = ctx.device["platform"] == "tpu"

    def start(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        os.makedirs(self.log_dir, exist_ok=True)
        t0 = time.perf_counter()
        jax.profiler.start_trace(self.log_dir, profiler_options=options)
        say("trace", start_seconds=round(time.perf_counter() - t0, 2))

    def stop(self) -> None:
        """Stop collecting.  Reading the file is `reduce`, which a
        serving driver leaves until its window is over: both block the
        one thread the engine runs on."""
        import jax

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        say("trace", stop_seconds=round(time.perf_counter() - t0, 2))

    def prime(self) -> None:
        """The first start of the profiler in a process takes seconds;
        a serving driver pays them in set-up, not inside its window."""
        self.start()
        self.stop()

    def reduce(self):
        """The benchmark's Trace from the newest file written."""
        from benchmark.reduce import xplane

        t0 = time.perf_counter()
        try:
            trace = xplane.load(xplane.find_xplane(self.log_dir))
        except ValueError:
            if self.required:
                raise
            return None
        say("trace", file=xplane.find_xplane(self.log_dir),
            reduce_seconds=round(time.perf_counter() - t0, 2),
            devices=len(trace.devices), window_s=trace.window_s,
            host_spans=len(trace.host_spans))
        return trace


def span(name: str):
    """A host span on the profiler's own clock (a no-op when no trace
    is being taken)."""
    import jax

    return jax.profiler.TraceAnnotation(name)
