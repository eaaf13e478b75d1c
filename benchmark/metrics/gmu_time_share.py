"""Device self time under scope ``gmu`` (a Gated Memory Unit's two
products and its gate over the memory an earlier layer left) over the
decode and prefill programs', %.  A program without the scope gives
nothing to read."""
from benchmark.reduce import program


def read(run):
    table = program.device_table(run)
    if not table or "gmu" not in table["scopes"]:
        return None
    return program.share_of(table, "gmu")
