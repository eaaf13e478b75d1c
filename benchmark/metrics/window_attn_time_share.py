"""Device self time under scope ``attn_window`` (the layers that attend
a bounded window: their projections, rotary, scores over the slot's
ring or the prefill's band, per-head gate and output projection) over
the decode and prefill programs', %.  A program without the scope gives
nothing to read."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "attn_window")
