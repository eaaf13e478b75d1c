"""Device self time under scope ``attn_cross`` (the layers that keep no
K/V of their own and attend another layer's: their query projection,
their walk of the shared pool, the differential combine and norm, their
output projection) over the decode and prefill programs', %.  A program
without the scope gives nothing to read."""
from benchmark.reduce import program


def read(run):
    table = program.device_table(run)
    if not table or "attn_cross" not in table["scopes"]:
        return None
    return program.share_of(table, "attn_cross")
