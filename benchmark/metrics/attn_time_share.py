"""Device self time of the train step's ops under scope ``attn``
(forward, backward and recompute alike) over the step's, %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "attn")
