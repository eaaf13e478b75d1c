"""(The linear-attention mixers' weight bytes + each decoding row's
matrices and convolution windows read and written once) / peak
bandwidth, over the decode program's device time under the scopes
``attn_linear`` and ``linear_state`` per step, %.  A decode step's
mixers are bound by memory: every weight meets a handful of rows, and
every row's state is read and written once.  The bytes are the family's
(``families/<family>.py linear_decode_bytes``), the rows the window's
own waves'; a family without such layers, or a program without the two
scopes, gives nothing to read."""
from benchmark import decode_scopes, readers
from benchmark.harness import say


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "linear_decode_bytes",
                   None)
    measured = need and decode_scopes.seconds_per_step(
        run, ("attn_linear", "linear_state"))
    waves = measured and readers._decode_waves(run)
    if not waves:
        return None
    measured_s, steps = measured
    rows = sum(waves) / len(waves)
    least_s = need(cell.config, rows) / run.ctx.peaks["hbm_bytes_per_s"]
    say("linear_attn_decode_roofline", least_ms=least_s * 1e3,
        measured_ms=measured_s * 1e3, steps=steps, rows=rows)
    return 100.0 * least_s / measured_s
