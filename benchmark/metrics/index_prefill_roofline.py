"""The operations a prefill's index scores need for the (query,
reachable key) pairs the traced window prefilled / the peak bf16 rate,
over the prefill program's device time under scope ``attn_index``, %.
The operations are the family's (``families/<family>.py
index_prefill_flops``: 2 x heads x width a pair a layer, whatever tiles
implement them and whatever the attention then skips); the pairs are
the launch records' (a tail of ``n_tail`` tokens behind ``prefix_len``
resident ones scores ``n_tail * prefix_len + n_tail * (n_tail + 1) /
2``; the prefill and chunk executions the window holds WHOLE,
``benchmark/reduce/launches.py``), and the time is of those executions
alone.  The scope holds the selection too (the search for each query's
last selected score), which the operations do not count: the share says
how far the whole of the indexer's prefill work is from its products at
peak.  A family without an indexer, a program without the scope or a
window without a whole prefill gives nothing to read."""
import dataclasses

from benchmark.harness import say
from benchmark.reduce import launches, program

SCOPE = "attn_index"


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "index_prefill_flops",
                   None)
    joined = need and launches.joined_run(run)
    if not joined:
        return None
    got = [p for p in joined.pairs
           if p.record["kind"] in launches.PREFILLS and p.whole]
    maps = program._registry_maps()
    maps = {name: maps[name] for name in {p.record["program"] for p in got}
            if name in maps}
    pairs = sum(p.record["n_tail"] * p.record.get("prefix_len", 0)
                + p.record["n_tail"] * (p.record["n_tail"] + 1) // 2
                for p in got)
    if not pairs or not maps:
        return None
    # the scope's time inside those executions alone: an op belongs to
    # the execution that holds its start (reduce/program.py)
    starts = {p.start for p in got}
    whole = dataclasses.replace(run.trace, devices=[
        dataclasses.replace(dev, modules=[
            m for m in dev.modules if m[1] in starts])
        for dev in run.trace.devices[:1]])
    table = program.scope_times(whole, maps)
    ns = table and table["scopes"].get(SCOPE, 0.0)
    if not ns:
        return None
    least_s = need(cell.config, pairs) / run.ctx.peaks["bf16_flops_per_s"]
    say("index_prefill_roofline", least_ms=least_s * 1e3,
        measured_ms=ns / 1e6, prefills=len(got), pairs=pairs)
    return 100.0 * least_s / (ns / 1e9)
