"""Positions the decode programs' queries ATTENDED over the positions
they could have reached, %, summed over rows, layers and the decode
programs the engine ran: the program's own counters
(``decode_common.INDEX_COUNTERS``, landed with each wave's tokens and
summed into ``serve_index_selected_total`` /
``serve_index_reachable_total``; warm-up's waves count too).  100 would
mean no context ever outgrew the selection: the traffic never reached
the mechanism.  A program without the counters (a family without an
indexer) gives nothing to read."""


def _total(snapshot, name: str) -> float:
    dump = snapshot.get(name) or {}
    return sum(value for tags, value in dump.get("values", ())
               if dict(map(tuple, tags)).get("program") == "decode")


def read(run):
    try:
        from ray_tpu.util.metrics import _registry
    except ImportError:
        return None
    snapshot = _registry.snapshot()
    reachable = _total(snapshot, "serve_index_reachable_total")
    if not reachable:
        return None
    return 100.0 * _total(snapshot, "serve_index_selected_total") / reachable
