"""The least time the chip could take for the reads of a K/V pool that
several layers share, over the decode program's device time under the
scopes ``attn_full``, ``attn_cross`` and ``kv_pool`` per step, %.  The
least is (the attention weights of the layer that writes the pool and
of those that only read it + for each row its K and V, ``context``
positions, once for each reading layer) / peak bandwidth
(``families/<family>.py shared_kv_decode_bytes``, from the published
sizes alone): a decode column's attention is bound by what it reads.
Rows and contexts are the window's own waves', as
``metrics/attn_decode_roofline.py`` counts them.  ``kv_pool`` has to
be the shared pool's alone in such a family's decode step: per-slot
rings of window layers, which the least leaves out, are scoped with
their layers (``models/phi4flash_decode.py``).  A family without the
function, or a program without the scope ``attn_cross``, gives nothing
to read."""
from benchmark import decode_scopes, readers
from benchmark.harness import say

SCOPES = ("attn_full", "attn_cross", "kv_pool")


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "shared_kv_decode_bytes",
                   None)
    if not need or not decode_scopes.seconds_per_step(run, SCOPES[1:2]):
        return None
    measured_s, steps = decode_scopes.seconds_per_step(run, SCOPES)
    waves = {}
    for r in readers._measured(run):
        for k, t in enumerate(r.get("token_ts") or ()):
            if k and run.t0 <= t <= run.t1:
                waves.setdefault(t, []).append(r["prompt_len"] + k)
    if not waves:
        return None
    least_s = sum(need(cell.config, contexts) for contexts in waves.values()) \
        / len(waves) / run.ctx.peaks["hbm_bytes_per_s"]
    say("shared_kv_decode_roofline", least_ms=least_s * 1e3,
        measured_ms=measured_s * 1e3, steps=steps,
        rows=sum(map(len, waves.values())) / len(waves),
        positions=sum(map(sum, waves.values())) / len(waves))
    return 100.0 * least_s / measured_s
