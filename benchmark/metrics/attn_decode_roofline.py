"""The least time the chip could take for a decode step's attention
over the decode program's device time under the scopes ``attn_full``,
``attn_window`` and ``kv_pool`` per step, %.  The least is (every
layer's attention weights + for each row its K and V: ``context``
positions of each full layer, ``min(context, window)`` of each window
layer) / peak bandwidth (``families/<family>.py attn_decode_bytes``,
from the published sizes alone, the same whatever implements the
attention): a decode column's attention is bound by what it reads.
Rows and contexts are the window's own waves', as
``metrics/mla_decode_roofline.py`` counts them.  A family without the
function, or a program without the scopes, gives nothing to read."""
from benchmark import decode_scopes, readers
from benchmark.harness import say


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "attn_decode_bytes", None)
    measured = need and decode_scopes.seconds_per_step(
        run, ("attn_full", "attn_window", "kv_pool"))
    if not measured:
        return None
    measured_s, steps = measured
    waves = {}
    for r in readers._measured(run):
        for k, t in enumerate(r.get("token_ts") or ()):
            if k and run.t0 <= t <= run.t1:
                waves.setdefault(t, []).append(r["prompt_len"] + k)
    if not waves:
        return None
    least_s = sum(need(cell.config, contexts) for contexts in waves.values()) \
        / len(waves) / run.ctx.peaks["hbm_bytes_per_s"]
    say("attn_decode_roofline", least_ms=least_s * 1e3,
        measured_ms=measured_s * 1e3, steps=steps,
        rows=sum(map(len, waves.values())) / len(waves),
        positions=sum(map(sum, waves.values())) / len(waves))
    return 100.0 * least_s / measured_s
