"""Device self time under the scopes of a sparse expert layer,
``moe_router`` (scores, top-k) and ``moe_experts`` (sort, grouped
matmuls, combine), over the decode and prefill programs', %.  The
shared expert is a dense MLP and counts under ``mlp``."""
from benchmark.reduce import program


def read(run):
    router = program.scope_share(run, "moe_router")
    experts = program.scope_share(run, "moe_experts")
    if router is None and experts is None:
        return None
    return (router or 0.0) + (experts or 0.0)
