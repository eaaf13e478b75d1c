"""Device self time under scope ``linear_state`` (reads and writes of
the linear-attention layers' per-slot matrices and convolution windows
and of their snapshot pool) over the decode and prefill programs', %.
A program without the scope gives nothing to read."""
from benchmark.reduce import program


def read(run):
    table = program.device_table(run)
    if not table or "linear_state" not in table["scopes"]:
        return None
    return program.share_of(table, "linear_state")
