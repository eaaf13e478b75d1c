"""Device self time under scope ``ssm_state`` (reads and writes of the
per-slot recurrent state and of its snapshot pool) over the decode and
prefill programs', %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "ssm_state")
