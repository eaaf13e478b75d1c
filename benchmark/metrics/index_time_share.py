"""Device self time under scope ``attn_index`` (a learned indexer: its
three projections, its key's LayerNorm and rotary, the index scores,
the top-k, and the mask or the gather's indices built from it) over the
decode and prefill programs', %.  A program without the scope gives
nothing to read."""
from benchmark.reduce import program


def read(run):
    table = program.device_table(run)
    if not table or "attn_index" not in table["scopes"]:
        return None
    return program.share_of(table, "attn_index")
