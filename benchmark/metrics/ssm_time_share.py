"""Device self time under scope ``ssm`` (a Mamba mixer's projections,
convolution, scan and gate) over the decode and prefill programs', %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "ssm")
