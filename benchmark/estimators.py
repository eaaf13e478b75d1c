"""The arithmetic between raw times and a metric.  Pure Python on
lists of floats, so the tests can feed it synthetic times."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between
    order statistics (numpy's default), so that one sample moving
    across a rank moves the value smoothly, not by a whole gap."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    rank = (len(xs) - 1) * q / 100.0
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def whole_step_rate(fences: Sequence[float], work_per_step: float
                    ) -> Tuple[float, int, float]:
    """Work per second over whole steps between two fences.

    ``fences[i]`` is the host time at which step i's result became
    ready; ``fences[0]`` belongs to the last warm-up step.  n steps
    completed between the first and the last fence, so the rate is
    ``n * work / (t_n - t_0)``: no partial step, no dispatch and no
    drain time enters either side.  Returns (rate, n, elapsed)."""
    if len(fences) < 2:
        raise ValueError("need the warm-up fence and one step's fence")
    n = len(fences) - 1
    elapsed = fences[-1] - fences[0]
    if elapsed <= 0:
        raise ValueError(f"fences do not advance: {elapsed}")
    return n * work_per_step / elapsed, n, elapsed


def step_times(fences: Sequence[float]) -> List[float]:
    return [b - a for a, b in zip(fences, fences[1:])]


def step_time_summary(fences: Sequence[float]) -> Dict[str, float]:
    """min / median / max step time and the index of the slowest."""
    d = step_times(fences)
    return {"n": len(d), "min_ms": min(d) * 1e3,
            "p50_ms": percentile(d, 50) * 1e3, "max_ms": max(d) * 1e3,
            "slowest_step": d.index(max(d))}


def should_stop(fences: Sequence[float], seconds: float) -> bool:
    """Stop dispatching once the newest fence is `seconds` past the
    warm-up fence."""
    return fences[-1] - fences[0] >= seconds


def ttft_ms(first_token: float, due: float) -> float:
    """Time to first token from when the request was DUE, not from when
    the generator got round to sending it: a stall of the generator or
    the engine's loop is then charged to the requests it delayed."""
    return (first_token - due) * 1e3


def token_gaps_ms(token_ts: Sequence[float]) -> List[float]:
    return [(b - a) * 1e3 for a, b in zip(token_ts, token_ts[1:])]


def emission_rate(stamps: Sequence[float], t_start: float, t_end: float
                  ) -> Optional[Tuple[float, int, float]]:
    """Tokens per second over the whole window: the tokens whose own
    emission stamp lies in (t_start, t_end], over the window's length.

    The clock fixes both ends and the data neither, so a stall anywhere
    in the window -- before the first token, in the middle, or running
    on to its end -- leaves fewer tokens over the same seconds and
    lowers the rate.  A request cut by the window's end counts what it
    produced inside it.  Tokens leave in waves (a decode step's share
    one stamp), so the count moves by a whole wave with where the cut
    falls: one wave in the window's some three hundred.  No token in
    the window: nothing to read, None.  Returns (rate, tokens, span)."""
    span = t_end - t_start
    n = sum(1 for t in stamps if t_start < t <= t_end)
    if n == 0 or span <= 0:
        return None
    return n / span, n, span
