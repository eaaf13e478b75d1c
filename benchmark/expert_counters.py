"""The program's expert counters, as a metric reader finds them.

A family with a sparse expert layer counts, on the device, what its
routing did on this chip in each fused program; the serving engine
lands the counts with the program's tokens and sums them by program
kind into the process's metric registry (``serve_expert_*``,
``ray_tpu/serve/telemetry.py``; ``docs/observability.md``).  A driver
keeps no engine, so the readers take the sums from that registry.
They run from the engine's start, warm-up included, whose waves hold
fewer rows than the window's: a mean over programs is diluted by them,
downward for the experts touched.

Against a program without these counters (the parent of PR 32, a
family without experts) `means` finds nothing and returns None: the
metric is left out of the line.
"""

from __future__ import annotations

from typing import Dict, Optional

_SUMS = {"assignments_local": "serve_expert_assignments_local_total",
         "experts_touched_share": "serve_expert_touched_share_sum",
         "load_max_over_mean": "serve_expert_load_max_over_mean_sum"}
_PROGRAMS = "serve_expert_programs_total"


def _total(snapshot, name: str, program: str) -> float:
    dump = snapshot.get(name) or {}
    return sum(value for tags, value in dump.get("values", ())
               if dict(map(tuple, tags)).get("program") == program)


def means(program: str) -> Optional[Dict[str, float]]:
    """Per-program means of the expert counters over the fused programs
    of kind `program` ("decode" or "prefill"), with their number."""
    try:
        from ray_tpu.util.metrics import _registry
    except ImportError:
        return None
    snapshot = _registry.snapshot()
    n = _total(snapshot, _PROGRAMS, program)
    if not n:
        return None
    out = {key: _total(snapshot, name, program) / n
           for key, name in _SUMS.items()}
    out["programs"] = n
    return out
