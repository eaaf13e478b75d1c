"""Bytes the cache holds reserved for the resident requests (pool
blocks of the layers kept at full reach, per-slot windows of the layers
that keep a bounded window) over what every layer at full reach would
reserve for the same requests, %, summed over the paged decode waves
the engine landed (``serve_kv_reach_*``, ``ray_tpu/serve/telemetry.py``
``record_kv_reach``; from the engine's start, warm-up included).  100
for a family without window layers; against a program without the
counters there is nothing to read."""


def _total(snapshot, name: str) -> float:
    dump = snapshot.get(name) or {}
    return sum(value for _tags, value in dump.get("values", ()))


def read(run):
    try:
        from ray_tpu.util.metrics import _registry
    except ImportError:
        return None
    snapshot = _registry.snapshot()
    full = _total(snapshot, "serve_kv_reach_full_bytes_total")
    if not full:
        return None
    held = _total(snapshot, "serve_kv_reach_pool_bytes_total") \
        + _total(snapshot, "serve_kv_reach_window_bytes_total")
    return 100.0 * held / full
