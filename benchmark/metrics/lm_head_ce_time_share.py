"""Device self time of the train step's ops under scope ``lm_head_ce``
(tied logits and cross-entropy) over the step's, %."""
from benchmark.reduce import program


def read(run):
    return program.scope_share(run, "lm_head_ce")
