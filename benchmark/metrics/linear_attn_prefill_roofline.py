"""The operations the linear-attention mixers need for the prompt
tokens the traced window prefilled / the peak bf16 rate, over the
prefill program's device time under the scopes ``attn_linear`` and
``linear_state``, %.  The operations are the family's
(``families/<family>.py linear_prefill_flops``: 2 per matmul parameter a
token and the recurrence's own work, the same whatever chunk size
implements it); the tokens are the launch records' (``n_tail`` of the
prefill and chunk executions the window holds WHOLE,
``benchmark/reduce/launches.py``), and the time is of those executions
alone.  A family without such layers, a program without the scopes or
a window without a whole prefill gives nothing to read."""
import dataclasses

from benchmark.harness import say
from benchmark.reduce import launches, program

SCOPES = ("attn_linear", "linear_state")


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "linear_prefill_flops",
                   None)
    joined = need and launches.joined_run(run)
    if not joined:
        return None
    got = [p for p in joined.pairs
           if p.record["kind"] in launches.PREFILLS and p.whole]
    maps = program._registry_maps()
    maps = {name: maps[name] for name in {p.record["program"] for p in got}
            if name in maps}
    tokens = sum(p.record["n_tail"] for p in got)
    if not tokens or not maps:
        return None
    # the scopes' time inside those executions alone: an op belongs to
    # the execution that holds its start (reduce/program.py)
    starts = {p.start for p in got}
    whole = dataclasses.replace(run.trace, devices=[
        dataclasses.replace(dev, modules=[
            m for m in dev.modules if m[1] in starts])
        for dev in run.trace.devices[:1]])
    table = program.scope_times(whole, maps)
    ns = table and sum(table["scopes"].get(s, 0.0) for s in SCOPES)
    if not ns:
        return None
    least_s = need(cell.config, tokens) / run.ctx.peaks["bf16_flops_per_s"]
    say("linear_attn_prefill_roofline", least_ms=least_s * 1e3,
        measured_ms=ns / 1e6, prefills=len(got), tokens=tokens)
    return 100.0 * least_s / (ns / 1e9)
