"""Bytes of the routed experts a decode step TOUCHES / peak bandwidth,
over the decode program's device time under scope ``moe_experts`` per
step, %.  A decode wave gives an expert one or two rows, so its grouped
matmuls are bound by reading the weights of the experts some row chose:
never the experts held.  The touched share is the program's own counter
(``benchmark/expert_counters.py``; warm-up's thinner waves pull it
down, so the share reads low rather than high), the bytes the family's
(``families/<family>.py expert_bytes``); a family without experts, or a
program without the scope, gives nothing to read."""
from benchmark import decode_scopes, expert_counters
from benchmark.harness import say


def read(run):
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "expert_bytes", None)
    measured = need and decode_scopes.seconds_per_step(run, ("moe_experts",))
    counted = measured and expert_counters.means("decode")
    if not counted:
        return None
    measured_s, steps = measured
    least_s = need(cell.config, counted["experts_touched_share"]) \
        / run.ctx.peaks["hbm_bytes_per_s"]
    say("moe_expert_roofline", least_ms=least_s * 1e3,
        measured_ms=measured_s * 1e3, steps=steps,
        touched_share=counted["experts_touched_share"],
        assignments_local=counted["assignments_local"])
    return 100.0 * least_s / measured_s
