"""The least time the chip could take for a decode step's latent
attention over the decode program's device time under the scopes
``mla`` and ``kv_pool`` per step, %.  The least is the larger of (every
layer's MLA weights + the latents of each position attended) / peak
bandwidth and the absorbed path's operations / peak bf16 rate
(``families/<family>.py mla_decode_bytes`` and ``mla_decode_flops``):
at 121 FLOP a latent byte the two lie close on this chip.  Positions
attended and rows are the window's own waves'.  A family without MLA, or
a program without the scope, gives nothing to read."""
from benchmark import decode_scopes, readers
from benchmark.harness import say


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    family = getattr(cell, "family", None)
    need_bytes = getattr(family, "mla_decode_bytes", None)
    need_flops = getattr(family, "mla_decode_flops", None)
    measured = need_bytes and need_flops and \
        decode_scopes.seconds_per_step(run, ("mla", "kv_pool"))
    if not measured:
        return None
    measured_s, steps = measured
    attended, waves = 0.0, {}
    for r in readers._measured(run):
        for k, t in enumerate(r.get("token_ts") or ()):
            if k and run.t0 <= t <= run.t1:
                attended += r["prompt_len"] + k
                waves[t] = waves.get(t, 0) + 1
    if not waves:
        return None
    rows = sum(waves.values()) / len(waves)
    positions = attended / len(waves)
    by_bytes = need_bytes(cell.config, positions) \
        / run.ctx.peaks["hbm_bytes_per_s"]
    by_flops = need_flops(cell.config, rows, positions) \
        / run.ctx.peaks["bf16_flops_per_s"]
    say("mla_decode_roofline", by_bytes_ms=by_bytes * 1e3,
        by_flops_ms=by_flops * 1e3, measured_ms=measured_s * 1e3,
        steps=steps, rows=rows, positions=positions)
    return 100.0 * max(by_bytes, by_flops) / measured_s
