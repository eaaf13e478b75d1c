"""The least time the chip could take for a decode step's SPARSE latent
attention, indexer and all, over the decode program's device time under
the scopes ``attn_index``, ``mla`` and ``kv_pool`` per step, %.  The
least is the larger of (every layer's MLA and indexer weights + the
index key of EVERY position of the rows' contexts + the latents of the
positions SELECTED) / peak bandwidth and the operations of the same /
peak bf16 rate (``families/<family>.py sparse_decode_bytes`` and
``sparse_decode_flops``).  Rows, positions and the selected
(``min(context, index_topk)`` a row) are the window's own waves'.  A
family without an indexer, or a program without the scope, gives
nothing to read."""
from benchmark import decode_scopes, readers
from benchmark.harness import say

SCOPES = ("attn_index", "mla", "kv_pool")


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    family = getattr(cell, "family", None)
    need_bytes = getattr(family, "sparse_decode_bytes", None)
    need_flops = getattr(family, "sparse_decode_flops", None)
    measured = need_bytes and need_flops and \
        decode_scopes.seconds_per_step(run, SCOPES)
    if not measured:
        return None
    measured_s, steps = measured
    topk = family.attention_shape(cell.config)["index_topk"]
    reached, selected, waves = 0.0, 0.0, {}
    for r in readers._measured(run):
        for k, t in enumerate(r.get("token_ts") or ()):
            if k and run.t0 <= t <= run.t1:
                reached += r["prompt_len"] + k
                selected += min(r["prompt_len"] + k, topk)
                waves[t] = waves.get(t, 0) + 1
    if not waves:
        return None
    rows = sum(waves.values()) / len(waves)
    positions, selected = reached / len(waves), selected / len(waves)
    by_bytes = need_bytes(cell.config, rows, positions, selected) \
        / run.ctx.peaks["hbm_bytes_per_s"]
    by_flops = need_flops(cell.config, rows, positions, selected) \
        / run.ctx.peaks["bf16_flops_per_s"]
    say("sparse_attn_decode_roofline", by_bytes_ms=by_bytes * 1e3,
        by_flops_ms=by_flops * 1e3, measured_ms=measured_s * 1e3,
        steps=steps, rows=rows, positions=positions, selected=selected)
    return 100.0 * max(by_bytes, by_flops) / measured_s
