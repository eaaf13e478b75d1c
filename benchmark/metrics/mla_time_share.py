"""Device self time under scope ``mla`` (latent attention: the down and
up projections, RoPE, and both attention paths) over the decode and
prefill programs', %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "mla")
