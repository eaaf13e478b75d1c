"""State/observability API (reference analog:
python/ray/experimental/state/api.py + dashboard/state_aggregator.py:132
StateAPIManager — `ray list actors/tasks/...`, summaries)."""

from __future__ import annotations

from collections import Counter
from typing import Any, Dict, List, Optional

from ray_tpu._private import worker_context


def _gcs_call(method: str, payload: Optional[dict] = None):
    import ray_tpu

    ray_tpu._auto_init()
    cw = worker_context.core_worker()
    return cw.io.run(cw.gcs.call(method, payload or {}))


def list_nodes() -> List[Dict[str, Any]]:
    return [{"node_id": n["node_id"].hex(), "alive": n["alive"],
             "address": n["address"], "resources": n["resources_total"],
             "available": n.get("resources_available", {})}
            for n in _gcs_call("node_list")]


def list_actors() -> List[Dict[str, Any]]:
    return [{"actor_id": a["actor_id"].hex(), "name": a["name"],
             "state": a["state"],
             "node_id": a["node_id"].hex() if a.get("node_id") else "",
             "num_restarts": a.get("num_restarts", 0),
             "resources": a.get("resources", {})}
            for a in _gcs_call("actor_list")]


def list_tasks(limit: int = 10000) -> List[Dict[str, Any]]:
    """Finished-task events (start/end/worker); running tasks appear once
    their worker flushes (~1s)."""
    return _gcs_call("task_events_list", {"limit": limit})


def list_placement_groups() -> List[Dict[str, Any]]:
    return [{"pg_id": p["pg_id"].hex(), "name": p["name"],
             "state": p["state"], "strategy": p["strategy"],
             "bundles": p["bundles"]}
            for p in _gcs_call("pg_list")]


def summarize_tasks() -> Dict[str, Any]:
    events = list_tasks()
    by_name = Counter(e["name"] for e in events)
    total_s = sum(e["end"] - e["start"] for e in events)
    return {"total": len(events), "by_func_name": dict(by_name),
            "total_execution_s": round(total_s, 3)}


def summarize_actors() -> Dict[str, Any]:
    actors = list_actors()
    return {"total": len(actors),
            "by_state": dict(Counter(a["state"] for a in actors))}


def timeline(filename: Optional[str] = None) -> List[Dict[str, Any]]:
    """Chrome-trace events for chrome://tracing / Perfetto (reference:
    ray.timeline, _private/state.py:828 chrome_tracing_dump)."""
    events = list_tasks()
    trace = []
    for e in events:
        trace.append({
            "name": e["name"], "cat": "task", "ph": "X",
            "ts": e["start"] * 1e6, "dur": (e["end"] - e["start"]) * 1e6,
            "pid": e["pid"], "tid": e["worker_id"],
            "args": {"task_id": e["task_id"], "actor_id": e["actor_id"]},
        })
    if filename:
        import json

        with open(filename, "w") as f:
            json.dump(trace, f)
    return trace


class profile_device:
    """Capture an XLA/TPU device trace alongside the task timeline
    (reference gap noted in SURVEY §5.1: the reference merges Ray task
    events only; JAX's profiler captures the device side).

    Usage:
        with ray_tpu.util.state.profile_device("/tmp/trace"):
            train_step(...)
        ray_tpu.timeline("tasks.json")   # task-level chrome trace

    The device trace lands in TensorBoard/XProf format under `logdir`
    ("tensorboard --logdir" or xprof to view); the task timeline stays
    chrome-trace.  The two share wall-clock timestamps, so aligning a
    slow task with its device activity is a same-axis comparison.
    A profiler that will not start or stop raises: a run asked to trace
    that returns without a trace has not done what it was asked.

    The Python tracer is off and the host tracer at level 2, as the
    benchmark's profiler has them: the Python tracer alone wrote
    300,000 events in two seconds of serving, and level 2 is what keeps
    the program's own ``raytpu.*`` spans (docs/observability.md,
    "Scopes and phases").
    """

    def __init__(self, logdir: str):
        self.logdir = logdir

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False
