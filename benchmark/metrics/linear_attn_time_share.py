"""Device self time under scope ``attn_linear`` (a linear-attention
layer's projections, convolutions, gates, delta rule, output norm and
output projection) over the decode and prefill programs', %.  A program
without the scope gives nothing to read."""
from benchmark.reduce import program


def read(run):
    table = program.device_table(run)
    if not table or "attn_linear" not in table["scopes"]:
        return None
    return program.share_of(table, "attn_linear")
