"""Device self time of the run's programs that no part of the model
claims, %: no scope, a container (``loss_and_grad``, ``layer_scan``)
alone, or a name that did not check under its key.  The coverage of
the naming itself."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, program.UNSCOPED)
