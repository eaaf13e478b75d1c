"""Device self time of the train step's ops under scope ``optimizer``
over the step's, %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "optimizer")
