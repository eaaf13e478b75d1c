"""The reductions the metric files share.  Each file under
``benchmark/metrics/`` is one metric: it picks one of these and says
with which arguments.  A reader that finds nothing to read returns
None, and the harness leaves the metric out of the line."""

from __future__ import annotations

import collections
from typing import List, Optional

from benchmark import estimators, flops
from benchmark.harness import say
from benchmark.reduce import xplane

DECODE_PROGRAM = "jit_pool_step"
PREFILL_PROGRAM = "jit_paged_prefill_sample"
TRAIN_PROGRAM = "jit_step"


def _measured(run) -> List[dict]:
    return getattr(run, "rows", None) or []


# ---------------------------------------------------------------- host

def setup_s(run) -> float:
    return run.setup_s


def train_tokens_per_s_chip(run) -> Optional[float]:
    if getattr(run, "fences", None) is None:
        return None
    rate, _, _ = estimators.whole_step_rate(run.fences,
                                            run.tokens_per_step)
    return rate / run.chips


def train_step_ms(run, q: float) -> Optional[float]:
    if getattr(run, "fences", None) is None:
        return None
    return estimators.percentile(
        estimators.step_times(run.fences), q) * 1e3


def train_mfu(run) -> Optional[float]:
    rate = train_tokens_per_s_chip(run)
    if rate is None:
        return None
    return 100.0 * run.flops_per_token * rate \
        / run.ctx.peaks["bf16_flops_per_s"]


def serve_out_tokens_per_s(run) -> Optional[float]:
    if getattr(run, "stamps", None) is None:
        return None
    got = estimators.emission_rate(run.stamps, run.t0, run.t1)
    return None if got is None else got[0]


def ttft_ms(run, q: float) -> Optional[float]:
    xs = [estimators.ttft_ms(r["first_token"], r["due"])
          for r in _measured(run)
          if r.get("first_token") is not None and r.get("due") is not None]
    return estimators.percentile(xs, q) if xs else None


def gap_ms(run, q: float) -> Optional[float]:
    xs = [g for r in _measured(run)
          for g in estimators.token_gaps_ms(r.get("token_ts") or ())]
    return estimators.percentile(xs, q) if xs else None


def generator_lag_ms(run, q: float) -> Optional[float]:
    xs = [(r["sent"] - r["due"]) * 1e3 for r in _measured(run)
          if r.get("due") is not None]
    return estimators.percentile(xs, q) if xs else None


def queue_wait_ms(run, q: float) -> Optional[float]:
    xs = [(r["admit"] - r["enqueue"]) * 1e3 for r in _measured(run)
          if r.get("admit") is not None]
    return estimators.percentile(xs, q) if xs else None


def prefix_hit_share(run) -> Optional[float]:
    """Prompt tokens served from resident blocks over prompt tokens, %."""
    rows = [r for r in _measured(run) if r.get("admit") is not None]
    if not rows:
        return None
    served = sum(r["hit_blocks"] for r in rows) * run.engine.block
    return 100.0 * served / sum(r["prompt_len"] for r in rows)


def _decode_waves(run) -> List[int]:
    """Rows that produced a token in each decode wave of the window.  A
    wave's tokens carry one timestamp; a request's first token comes
    from its prefill and is left out."""
    waves = collections.Counter(
        t for r in _measured(run) for t in (r.get("token_ts") or ())[1:]
        if run.t0 <= t <= run.t1)
    return list(waves.values())


def slot_occupancy(run) -> Optional[float]:
    waves = _decode_waves(run)
    if not waves:
        return None
    return 100.0 * sum(waves) / len(waves) / run.engine.max_slots


def compiles_in_window(run) -> float:
    return float(run.compiles_in_window)


# --------------------------------------------------------------- trace

def _trace(run):
    return getattr(run, "trace", None)


def device_idle_share(run) -> Optional[float]:
    trace = _trace(run)
    if trace is None:
        return None
    return 100.0 * (1.0 - xplane.busy_s(trace) / trace.window_s)


def program_ms(run, program: str, q: float) -> Optional[float]:
    """Device time of one program per call, ms (first device)."""
    trace = _trace(run)
    if trace is None:
        return None
    calls = [d / 1e6 for _, _, d in xplane.module_events(trace, program)[0]]
    return estimators.percentile(calls, q) if calls else None


def program_time_share(run, program: str) -> Optional[float]:
    trace = _trace(run)
    if trace is None:
        return None
    calls = xplane.module_events(trace, program)
    ns = sum(d for dev in calls for _, _, d in dev) / len(calls)
    return 100.0 * ns / 1e9 / trace.window_s


def _step_seconds(trace) -> Optional[float]:
    """Device seconds inside the train program, averaged over devices."""
    calls = xplane.module_events(trace, TRAIN_PROGRAM)
    ns = sum(d for dev in calls for _, _, d in dev)
    return ns / len(calls) / 1e9 if ns else None


def _mosaic_seconds(trace) -> float:
    return sum(d for dev in trace.devices
               for text, _, d in dev.ops if xplane.is_mosaic(text)) \
        / len(trace.devices) / 1e9


def flash_time_share(run) -> Optional[float]:
    trace = _trace(run)
    if trace is None or not _step_seconds(trace):
        return None
    return 100.0 * _mosaic_seconds(trace) / _step_seconds(trace)


def flash_attention_roofline(run) -> Optional[float]:
    """The least time the chip could take for the attention the steps
    need (causal FLOPs and bytes from shapes, recompute not counted)
    over the Mosaic kernels' device time, %."""
    trace = _trace(run)
    if trace is None or getattr(run, "shapes", None) is None:
        return None
    n_steps = len(xplane.module_events(trace, TRAIN_PROGRAM)[0])
    measured = _mosaic_seconds(trace)
    if not n_steps or not measured:
        return None
    s = run.shapes
    bh = s["batch"] // run.chips * s["n_head"]
    unit = flops.flash_unit_flops(bh, s["seq"], s["head_dim"])
    need_flops = unit * sum(flops.FLASH_UNITS.values())
    need_bytes = sum(flops.flash_bytes(bh, s["seq"],
                                       s["head_dim"]).values())
    least, bound = flops.roofline_s(need_flops, need_bytes, run.ctx.peaks)
    say("flash_roofline", bound=bound, least_ms_per_layer=least * 1e3,
        measured_ms_per_layer=measured / n_steps / s["n_layer"] * 1e3)
    return 100.0 * least * s["n_layer"] * n_steps / measured


def collective_time_share(run) -> Optional[float]:
    """Device time with a collective in flight over the step time, %."""
    trace = _trace(run)
    if trace is None or not _step_seconds(trace):
        return None
    ns = 0.0
    for dev in trace.devices:
        spans = [(s, s + d) for text, s, d in dev.ops + dev.async_ops
                 if xplane.is_collective(text)]
        ns += xplane.total(xplane.union(spans))
    return 100.0 * ns / len(trace.devices) / 1e9 / _step_seconds(trace)


def collective_exposed_share(run) -> Optional[float]:
    """The part of it with no compute on that device: the core executes
    one instruction at a time, so while it sits in a collective's own
    instruction (a synchronous collective, or the ``-done`` that waits
    for an asynchronous one) nothing else runs."""
    trace = _trace(run)
    if trace is None or not _step_seconds(trace):
        return None
    ns = sum(d for dev in trace.devices for text, _, d in dev.ops
             if xplane.is_collective(text))
    return 100.0 * ns / len(trace.devices) / 1e9 / _step_seconds(trace)


def decode_hbm_roofline(run) -> Optional[float]:
    """(weight bytes + K/V bytes of the positions attended) / peak
    bandwidth over the decode program's device time per step, %.  A
    decode step is bound by memory: it multiplies every weight by a
    handful of rows."""
    step_ms = program_ms(run, DECODE_PROGRAM, 50)
    if step_ms is None:
        return None
    attended, waves = 0.0, set()
    for r in _measured(run):
        for k, t in enumerate(r.get("token_ts") or ()):
            if k and run.t0 <= t <= run.t1:
                attended += r["prompt_len"] + k
                waves.add(t)
    if not waves:
        return None
    cell = run.ctx.cell
    need = cell.family.decode_step_bytes(cell.config,
                                         attended / len(waves))
    least = need / run.ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms / 1e3)
