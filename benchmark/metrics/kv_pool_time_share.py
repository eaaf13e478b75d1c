"""Device self time under scope ``kv_pool`` (by name, or a pool-shaped
copy by shape) over the decode and prefill programs', %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "kv_pool")
