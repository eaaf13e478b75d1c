"""Device self time under the scopes ``attn_cross`` and ``gmu`` in the
PREFILL program over that program's device time, %.  Near zero while a
prefill's cross-decoder sees one token a row (the layers after the one
that fills the pool owe the cache nothing for a prompt's other
positions); it rises to those layers' share of the model if a change
runs them over every column: the guard on that.  A program without
either scope, or a window without a prefill, gives nothing to read."""
from benchmark import readers
from benchmark.reduce import program

SCOPES = ("attn_cross", "gmu")


def read(run):
    trace = getattr(run, "trace", None)
    prefill = program._registry_maps().get(readers.PREFILL_PROGRAM)
    if trace is None or not prefill:
        return None
    table = program.scope_times(trace, {readers.PREFILL_PROGRAM: prefill})
    if not table or not table["total"] \
            or not any(s in table["scopes"] for s in SCOPES):
        return None
    return 100.0 * sum(table["scopes"].get(s, 0.0) for s in SCOPES) \
        / table["total"]
