"""Device self time under scope ``sample`` over the decode and prefill
programs', %: a sixth reader of a scope, as a new mechanism's PR would
add one."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "sample")
