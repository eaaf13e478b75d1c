"""Device self time under scope ``attn_full`` (the layers that attend
the whole context: their projections, rotary, scores over the row's
blocks or the prefill's causal tiles, per-head gate and output
projection) over the decode and prefill programs', %.  A program
without the scope gives nothing to read."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "attn_full")
