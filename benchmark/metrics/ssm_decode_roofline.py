"""(The Mamba mixers' weight bytes + each decoding row's recurrent state
read and written) / peak bandwidth, over the decode program's device
time under the scopes ``ssm`` and ``ssm_state`` per step, %.  A decode
step's mixers are bound by memory: every weight meets a handful of
rows, and every row's state is read and written once.  The bytes are
the family's (``families/<family>.py ssm_decode_bytes``); a family
without recurrent state, or a program without the two scopes, gives
nothing to read."""
from benchmark import readers
from benchmark.harness import say
from benchmark.reduce import program, xplane


def read(run):
    trace = getattr(run, "trace", None)
    cell = getattr(getattr(run, "ctx", None), "cell", None)
    need = getattr(getattr(cell, "family", None), "ssm_decode_bytes", None)
    if trace is None or need is None:
        return None
    decode = program._registry_maps().get(readers.DECODE_PROGRAM)
    if not decode:
        return None
    table = program.scope_times(trace, {readers.DECODE_PROGRAM: decode})
    waves = readers._decode_waves(run)
    steps = len(xplane.module_events(trace, readers.DECODE_PROGRAM)[0])
    if not table or not waves or not steps:
        return None
    measured_s = sum(table["scopes"].get(s, 0.0)
                     for s in ("ssm", "ssm_state")) / 1e9 / steps
    if not measured_s:
        return None
    least_s = need(cell.config, sum(waves) / len(waves)) \
        / run.ctx.peaks["hbm_bytes_per_s"]
    say("ssm_decode_roofline", least_ms=least_s * 1e3,
                measured_ms=measured_s * 1e3, steps=steps,
                rows=sum(waves) / len(waves))
    return 100.0 * least_s / measured_s
