"""``benchmark/reduce/program.py`` on synthetic events: device time by
the program's own scopes, idle time split over the engine's phases, and
the host's part of an engine step."""

import types

import pytest

from benchmark.reduce import program as P
from benchmark.reduce.xplane import DeviceTrace, Trace
from ray_tpu._private.scopes import instruction_key

POOL = (48, 1024, 16, 25, 64)
US = 1000.0


def op(name, kind, start_us, dur_us, result="bf16[32,1600]{1,0}"):
    return (f"%{name} = {result} {kind}(%x)", start_us * US, dur_us * US)


def make_trace(ops, modules, t0=0.0, t1=None, n_devices=1):
    devs = [DeviceTrace(f"/device:TPU:{i}", list(ops), list(modules))
            for i in range(n_devices)]
    end = max(s + d for _, s, d in modules)
    return Trace(devs, [], t0 * US, (t1 * US) if t1 else end)


def serving_trace():
    """Two executions of ``jit_pool_step`` (0-100 us, 150-250 us) and
    one of a program nobody asked about."""
    modules = [("jit_pool_step(123)", 0.0, 100 * US),
               ("jit__threefry_split(9)", 110 * US, 5 * US),
               ("jit_pool_step(123)", 150 * US, 100 * US)]
    pool = "bf16[48,1024,16,25,64]{4,3,2,1,0:T(8,128)(2,1)}"
    layer = "bf16[1024,16,25,64]{3,2,1,0:T(8,128)(2,1)}"
    ops = []
    for base in (0, 150):
        ops += [
            op("while.1", "while", base, 100, "(bf16[32,1600]{1,0})"),
            op("fusion.7", "fusion", base + 0, 30),            # attn
            op("fusion.8", "fusion", base + 30, 20),           # mlp
            op("copy.3", "copy", base + 50, 25, pool),         # by shape
            op("bitcast_dynamic-update-slice_fusion", "fusion",
               base + 75, 5, layer),                           # by shape
            op("gather_fusion.2", "fusion", base + 80, 10),    # kv_pool
            op("copy.9", "copy", base + 90, 6),                # loose
        ]
    ops.append(op("fusion.7", "fusion", 111, 3))   # threefry's own op
    return make_trace(ops, modules, t1=250)


def keyed(scopes_by_name, trace=None):
    """A registry's scope map for `scopes_by_name`, with each
    instruction's key as the (synthetic) compiled text would give it."""
    texts = {P.instruction_name(t): t
             for t, _, _ in (trace or serving_trace()).devices[0].ops}
    return {name: {instruction_key(texts[name]): scope}
            for name, scope in scopes_by_name.items()}


NAMED = {"fusion.7": "attn", "fusion.8": "mlp",
         "gather_fusion.2": "kv_pool", "while.1": "layer_scan"}
MAPS = {"jit_pool_step": keyed(NAMED)}


def test_scope_shares_and_unscoped_sum_to_the_whole():
    table = P.scope_times(serving_trace(), MAPS, POOL)
    ns = table["scopes"]
    assert ns["attn"] == 60 * US and ns["mlp"] == 40 * US
    # 20 by name, 50 + 10 by shape
    assert ns["kv_pool"] == 80 * US and table["by_shape"] == 60 * US
    # the while's self time (what its body does not cover) is in the
    # scan and in no part of the model: a container alone is unscoped
    assert "layer_scan" not in ns
    assert table["why"] == {"layer_scan": 2 * 4 * US,
                            "no_scope": 12 * US}
    assert table["unscoped"] == 20 * US
    assert table["unscoped_ops"] == {"no_scope:copy": 12 * US,
                                     "layer_scan:while": 8 * US}
    assert table["total"] == 200 * US     # another program's op is out
    # one compiled signature in the trace; 60 by shape and 12 without
    # metadata found no name that checked
    assert table["signatures"] == {
        "jit_pool_step(123)": [200 * US, (200 - 60 - 12) * US]}
    shares = [P.share_of(table, s)
              for s in list(ns) + [P.UNSCOPED]]
    assert sum(shares) == pytest.approx(100.0, abs=1e-9)
    assert P.share_of(table, "kv_pool") == pytest.approx(40.0)
    assert P.share_of(table, "optimizer") == 0.0


def test_pool_shaped_copies_fall_to_kv_pool_only_by_shape():
    pool = "bf16[48,1024,16,25,64]{4,3,2,1,0}"
    assert P.is_pool_copy(f"%copy.3 = {pool} copy(%p)", POOL)
    assert P.is_pool_copy(
        f"%copy-start.3 = ({pool}, {pool}, u32[]) copy-start(%p)", POOL)
    assert P.is_pool_copy(
        "%constant_dynamic-update-slice_fusion = "
        "bf16[1024,16,25,64]{3,2,1,0} fusion(%a, %b)", POOL)
    assert P.is_pool_copy(          # one layer as the scan stacks it
        "%copy.47 = bf16[1,1024,16,25,64]{1,4,3,2,0:T(8,128)(2,1)S(1)} "
        "copy(%bitcast.119)", POOL)
    # another shape, another op, no pool known
    assert not P.is_pool_copy("%copy.4 = bf16[32,1600]{1,0} copy(%p)",
                              POOL)
    assert not P.is_pool_copy(f"%fusion.3 = {pool} fusion(%p)", POOL)
    assert not P.is_pool_copy(f"%copy.3 = {pool} copy(%p)", None)
    # a scope the program gave wins over the shape...
    named = keyed(dict(NAMED, **{"copy.3": "attn"}))
    table = P.scope_times(serving_trace(), {"jit_pool_step": named}, POOL)
    assert table["scopes"]["attn"] == (60 + 50) * US
    # ...except the layer scan's own, which is the pool out of the scan
    scanned = keyed(dict(NAMED, **{"copy.3": "layer_scan"}))
    table = P.scope_times(serving_trace(), {"jit_pool_step": scanned},
                          POOL)
    assert table["scopes"]["kv_pool"] == 80 * US
    # and without the pool's shape nothing is claimed by shape
    table = P.scope_times(serving_trace(), MAPS, None)
    assert table["by_shape"] == 0.0 and table["unscoped"] == 80 * US


def test_a_name_counts_only_where_its_key_checks():
    """XLA numbers each signature's instructions anew: ``fusion.7`` of
    another prefill bucket is another instruction.  The map holds every
    signature's entry under its key (result type and opcode); an event
    whose key is not there is unscoped, and says so."""
    other = {"fusion.7": {"bf16[64,1600]{1,0} fusion": "mlp"},
             "fusion.8": {"bf16[32,1600]{1,0} copy": "attn"}}
    table = P.scope_times(serving_trace(), {"jit_pool_step": other}, POOL)
    assert table["scopes"] == {"kv_pool": 60 * US}      # by shape alone
    assert table["why"]["mismatched"] == (60 + 40) * US
    ns, text, keys = table["mismatches"][0]       # the longest, for a look
    assert ns == 30 * US and text.startswith("%fusion.7 = ")
    assert keys == ["bf16[64,1600]{1,0} fusion"]
    # both signatures' entries side by side: each event finds its own
    both = keyed(NAMED)
    both["fusion.7"].update(other["fusion.7"])
    table = P.scope_times(serving_trace(), {"jit_pool_step": both}, POOL)
    assert table["scopes"]["attn"] == 60 * US
    assert "mismatched" not in table["why"]
    # one name and key under two scopes is nobody's
    both["fusion.7"] = {k: P.AMBIGUOUS for k in keyed(NAMED)["fusion.7"]}
    table = P.scope_times(serving_trace(), {"jit_pool_step": both}, POOL)
    assert table["why"]["ambiguous"] == 60 * US
    assert "attn" not in table["scopes"]


def test_scope_times_averages_devices_and_finds_nothing_politely():
    one = P.scope_times(serving_trace(), MAPS, POOL)
    four = serving_trace()
    four.devices = four.devices * 4
    assert P.scope_times(four, MAPS, POOL) == one
    assert P.scope_times(serving_trace(), {"jit_step": {}}, POOL) is None
    assert P.share_of(None, "attn") is None


def test_instruction_names_and_result_dims():
    text = "%fusion.12 = bf16[24,1024]{1,0:T(8,128)} fusion(%a), kind=kLoop"
    assert P.instruction_name(text) == "fusion.12"
    assert P.result_dims(text) == (24, 1024)
    assert P.instruction_name("%copy-start.61 = (f32[8]{0}) copy-start()") \
        == "copy-start.61"
    assert P.result_dims("%while.1 = (s32[], f32[4,2]{1,0}) while(%t)") \
        == ()
    assert P.result_dims("%x = token[] after-all()") == ()
    assert instruction_key(text) == "bf16[24,1024]{1,0:T(8,128)} fusion"
    # as a trace prints it (operand types) and as the compiled text does
    assert instruction_key(
        "%copy-done.61 = s32[24,1025]{1,0:T(8,128)S(1)} copy-done("
        "(s32[24,1025]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) %copy-start.61)") \
        == instruction_key(
            "%copy-done.61 = s32[24,1025]{1,0:T(8,128)S(1)} "
            "copy-done(%copy-start.61)") \
        == "s32[24,1025]{1,0:T(8,128)S(1)} copy-done"
    assert instruction_key(
        "%while.1 = (s32[]{:T(128)}, f32[4,2]{1,0}) while(%t), body=%b") \
        == "(s32[]{:T(128)}, f32[4,2]{1,0}) while"


# ----------------------------------------------------------------- host

def span(name, start_us, dur_us):
    return ("raytpu.engine." + name, start_us * US, dur_us * US)


def engine_spans(third=False):
    """Two steps.  First (0-100 us): admit 0-20 holding kv.reserve 2-6,
    prefill_dispatch 6-10 and prefill_fence 10-18; decode_dispatch
    22-30; decode_fence 30-90; emit 90-95; yield 95-100.  Second
    (100-140): a prefill only, no wave.  `third` (200-300): a wave and
    no prefill."""
    wave = [span("step", 200, 100), span("admit", 200, 1),
            span("rng_split", 201, 9), span("decode_dispatch", 212, 8),
            span("decode_fence", 220, 70), span("emit", 290, 5),
            span("yield", 295, 5)] if third else []
    return wave + [
        span("step", 0, 100),
        span("admit", 0, 2), span("kv.reserve", 2, 4),
        span("prefill_dispatch", 6, 4), span("prefill_fence", 10, 8),
        span("admit", 18, 2),
        span("decode_dispatch", 22, 8), span("decode_fence", 30, 60),
        span("emit", 90, 5), span("yield", 95, 5),
        span("step", 100, 40),
        span("admit", 100, 5), span("prefill_fence", 105, 30),
        span("yield", 136, 4),
        ("bench.send", 96 * US, 2 * US),
    ]


def test_engine_host_ms_leaves_the_fences_out():
    # a step that admits beside its wave pays a prefill's dispatch,
    # which is per request: only a wave alone counts, 100 - 70 us
    assert P.engine_host_ms(engine_spans()) == []
    assert P.engine_host_ms(engine_spans(third=True)) \
        == [pytest.approx(0.030)]
    assert P.engine_host_ms([]) == []
    assert P.engine_host_ms([span("admit", 0, 5)]) == []


def test_idle_is_split_over_the_phases_by_overlap():
    # the device runs 12-16, 31-88 and 106-134; idle elsewhere in 0-150
    ops = [op("fusion.1", "fusion", 12, 4), op("fusion.2", "fusion", 31, 57),
           op("fusion.3", "fusion", 106, 28)]
    trace = make_trace(ops, [("jit_pool_step(1)", 12 * US, 122 * US)],
                       t0=0, t1=150)
    table = P.idle_by_phase(trace, engine_spans())
    assert table["idle_ns"] == (150 - 4 - 57 - 28) * US
    idle = {k: v[0] / US for k, v in table["phases"].items()}
    host = {k: v[1] / US for k, v in table["phases"].items()}
    # a gap that spans three phases is split between them, not given to
    # the one that covers most of it: 0-12 is admit 0-2, kv.reserve 2-6,
    # prefill_dispatch 6-10, prefill_fence 10-12
    assert idle["admit"] == 2 + 2 + 5          # 0-2, 18-20, 100-105
    assert idle["kv.reserve"] == 4
    assert idle["prefill_dispatch"] == 4
    assert idle["prefill_fence"] == 2 + 2 + 1 + 1   # 10-12,16-18,105-106,134-135
    assert idle["decode_dispatch"] == 8
    assert idle["decode_fence"] == 1 + 2            # 30-31, 88-90
    assert idle["emit"] == 5 and idle["yield"] == 5 + 4
    # what of a step no leaf covers is the loop's own: 20-22, 135-136
    assert idle["loop"] == 2 + 1 and host["loop"] == 3
    assert host["decode_fence"] == 60 and host["admit"] == 9
    # 140-150 lies under no step: the engine's spans do not explain it
    under = sum(idle.values())
    assert under == table["idle_ns"] / US - 10
    # attributed is idle under a phase in which the host works: the
    # fences (the host waits) and the loop's fragments are located,
    # not explained, or the share would be true by construction
    split = P.idle_split(table)
    whole = under + 10
    assert split["fences"] == pytest.approx(100.0 * (6 + 3) / whole)
    assert split["loop"] == pytest.approx(100.0 * 3 / whole)
    assert split["outside"] == pytest.approx(100.0 * 10 / whole)
    assert split["attributed"] == pytest.approx(
        100.0 * (under - 9 - 3) / whole)
    assert sum(split.values()) == pytest.approx(100.0)
    assert P.idle_attributed_share_of(table) == split["attributed"]
    assert P.idle_by_phase(trace, [("bench.send", 0.0, 5.0)]) is None


def test_spans_are_clipped_to_the_window():
    ops = [op("fusion.1", "fusion", 50, 10)]
    trace = make_trace(ops, [("jit_pool_step(1)", 50 * US, 10 * US)],
                       t0=40, t1=70)
    table = P.idle_by_phase(trace, engine_spans())
    assert table["idle_ns"] == 20 * US
    assert set(table["phases"]) == {"decode_fence"}
    assert table["phases"]["decode_fence"] == [20 * US, 30 * US]
    assert P.idle_attributed_share_of(table) == 0.0     # all in a fence


def test_readers_find_nothing_on_a_run_without_a_trace(monkeypatch):
    """The parent of PR 25, the CPU rehearsal, an untraced run: every
    reader returns None and nothing raises."""
    # a registry without scope_map (the parent's) gives no maps; the
    # process-wide one may hold another test's programs
    from ray_tpu._private import device_stats

    monkeypatch.setattr(device_stats, "get_registry", object)
    assert P._registry_maps() == {}
    empty = types.SimpleNamespace(setup_s=1.0, compiles_in_window=0)
    assert P.scope_share(empty, "attn") is None
    assert P.engine_host_ms_per_step(empty) is None
    assert P.idle_attributed_share(empty) is None
    traced = types.SimpleNamespace(
        trace=serving_trace(),
        ctx=types.SimpleNamespace(trace_dir="/nonexistent"))
    # a trace, but a program that keeps no scope map and wrote no spans
    assert P.scope_share(traced, "attn") is None
    assert P.idle_attributed_share(traced) is None
    assert P.engine_host_ms_per_step(traced) is None


def test_the_metric_files_quote_registered_scopes():
    """Each ``*_time_share`` reader names a scope of the program's
    registry (``ray_tpu/_private/scopes.py``) that is no container.  A
    new mechanism's PR adds a reader for its scope; the five of PR 25
    stay, by name."""
    import os
    import re

    from benchmark.cells import HERE
    from ray_tpu._private import scopes

    quoted = {}
    for name in sorted(os.listdir(os.path.join(HERE, "metrics"))):
        with open(os.path.join(HERE, "metrics", name)) as f:
            text = f.read()
        for scope in re.findall(r'scope_share\(run, "(\w+)"\)', text):
            quoted[name] = scope
    assert {"attn_time_share.py": "attn", "mlp_time_share.py": "mlp",
            "lm_head_ce_time_share.py": "lm_head_ce",
            "optimizer_time_share.py": "optimizer",
            "kv_pool_time_share.py": "kv_pool"}.items() <= quoted.items()
    assert set(quoted.values()) <= set(scopes.DEVICE_SCOPES) \
        - set(scopes.CONTAINER_SCOPES)


def test_the_readers_names_are_the_programs():
    """``program.py`` spells the program's names again (it has to load
    against a checkout without ``_private/scopes.py``): every one of
    them equals the registry's constant."""
    from ray_tpu._private import scopes

    def engine(phase):
        return scopes.span_name(scopes.ENGINE, phase)

    assert P.KV_POOL == scopes.KV_POOL
    assert P.LAYER_SCAN == scopes.LAYER_SCAN
    assert set(P.CONTAINERS) == set(scopes.CONTAINER_SCOPES)
    assert P.AMBIGUOUS == scopes.AMBIGUOUS
    assert P.LOOP == scopes.LOOP
    assert P.SPAN_PREFIX == scopes.SPAN_PREFIX
    assert P.ENGINE_PREFIX == engine("")
    assert P.STEP_SPAN == engine(scopes.STEP)
    assert P.DECODE_FENCE == engine("decode_fence")
    assert set(P.FENCES) == {engine(f) for f in scopes.ENGINE_FENCES}
    assert set(P.PREFILLS) == {engine(p) for p in scopes.ENGINE_PHASES
                               if p.startswith("prefill_")}
    assert {p[len(P.ENGINE_PREFIX):] for p in P.FENCES + P.PREFILLS} \
        <= set(scopes.ENGINE_PHASES)
    # no reason a reader gives for "unscoped" is a scope's name
    assert not {P.NO_SCOPE, P.MISMATCHED, P.UNSCOPED} \
        & set(scopes.DEVICE_SCOPES)
