"""What a roofline reader of the decode program starts from: the device
time of the decode program's own scopes per step, from a run's trace
and the running program's scope map.  Against a program that keeps no
scope map, or a run without a trace, there is nothing: None."""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

from benchmark import readers
from benchmark.reduce import program, xplane


def seconds_per_step(run, scopes: Sequence[str]
                     ) -> Optional[Tuple[float, int]]:
    """(device seconds under `scopes` per decode step, steps traced)."""
    trace = getattr(run, "trace", None)
    if trace is None:
        return None
    decode = program._registry_maps().get(readers.DECODE_PROGRAM)
    if not decode:
        return None
    table = program.scope_times(trace, {readers.DECODE_PROGRAM: decode})
    steps = len(xplane.module_events(trace, readers.DECODE_PROGRAM)[0])
    if not table or not steps:
        return None
    ns = sum(table["scopes"].get(s, 0.0) for s in scopes)
    return (ns / 1e9 / steps, steps) if ns else None
