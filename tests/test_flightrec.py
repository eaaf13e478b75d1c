"""Flight recorder + SLO burn-rate engine: ring-journal semantics,
deterministic burn-rate math, the end-to-end breach → postmortem-dump
pipeline through a real paged continuous engine, the postmortem CLI,
and the hot-path overhead guard.

The e2e test is the acceptance path: a deliberately impossible
SLOConfig (sub-microsecond targets) forces a breach on the first
requests, the watchdog writes a dump mid-run, and the CLI reads it
back in a subprocess — the whole loop a production postmortem walks.
"""

import asyncio
import json
import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private.flightrec import (FlightRecorder,
                                        default_dump_dir)  # noqa: E402
from ray_tpu.serve.llm import build_llm_deployment  # noqa: E402
from ray_tpu.serve.slo import SLOConfig, SLOTracker  # noqa: E402
from ray_tpu.tools.flightrec import (filter_events, load_dump,
                                     report_lines, sweepjson_records,
                                     trace_events)  # noqa: E402
from ray_tpu.tools.flightrec import main as flightrec_main  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build(**kw):
    kw.setdefault("max_new_tokens", 4)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("max_slots", 2)
    kw.setdefault("config_overrides", _OVR)
    return build_llm_deployment("gpt2", "nano", **kw)


def _drive(dep, prompts, timeout=300):
    async def main():
        inst = dep.func_or_class()
        try:
            outs = await asyncio.wait_for(
                asyncio.gather(*[inst(p) for p in prompts]), timeout)
            stats = inst.engine_stats()
        finally:
            inst.shutdown_engine()
        return outs, stats

    return asyncio.run(main())


def _prompts(n, lo=8, hi=14, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 50, size=rng.randint(lo, hi))
            .astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# FlightRecorder ring semantics
# ---------------------------------------------------------------------------

def test_ring_saturation_counts_drops():
    rec = FlightRecorder("t", capacity=8, enabled=True)
    for i in range(20):
        rec.record("step", i=i)
    assert rec.recorded == 20
    assert rec.retained == 8
    assert rec.dropped == 12
    snap = rec.snapshot()
    # oldest events forgotten, survivors in order with global seq
    assert [e["seq"] for e in snap] == list(range(13, 21))
    assert [e["i"] for e in snap] == list(range(12, 20))
    assert rec.counts_by_kind() == {"step": 8}
    st = rec.stats()
    assert st["enabled"] and st["capacity"] == 8
    assert st["recorded"] == 20 and st["dropped"] == 12


def test_injectable_ts_rebases_to_start():
    rec = FlightRecorder("t", enabled=True)
    rec.record("admit", ts=rec.t0 + 1.5, req="r0")
    (e,) = rec.snapshot()
    assert e["t_s"] == pytest.approx(1.5)
    assert e["kind"] == "admit" and e["req"] == "r0"


def test_env_disable(monkeypatch, tmp_path):
    monkeypatch.setenv("RAYTPU_FLIGHTREC", "0")
    rec = FlightRecorder("t")
    rec.record("step")
    assert not rec.enabled
    assert rec.recorded == 0 and rec.snapshot() == []
    assert rec.dump(reason="x") is None
    assert rec.stats()["dumps"] == []
    # explicit override beats the env
    assert FlightRecorder("t", enabled=True).enabled


def test_dump_roundtrip(tmp_path):
    rec = FlightRecorder("eng:0", capacity=4, enabled=True)
    rec.dump_dir = str(tmp_path)
    for i in range(6):
        rec.record("step", dur_ms=float(i))
    path = rec.dump(reason="unit/test",
                    context={"note": "hi"})
    assert path is not None and os.path.dirname(path) == str(tmp_path)
    assert rec.dumps == [path] and rec.stats()["dumps"] == [path]
    doc = load_dump(path)
    assert doc["version"] == 1
    assert doc["source"] == "eng:0"
    assert doc["reason"] == "unit/test"
    assert doc["events_recorded"] == 6
    assert doc["events_retained"] == 4
    assert doc["events_dropped"] == 2
    assert doc["counts_by_kind"] == {"step": 4}
    assert doc["context"] == {"note": "hi"}
    assert len(doc["events"]) == 4
    # second dump gets a distinct filename from the per-recorder counter
    path2 = rec.dump(reason="unit/test")
    assert path2 != path


def test_default_dump_dir_env(monkeypatch, tmp_path):
    monkeypatch.setenv("RAYTPU_FLIGHTREC_DIR", str(tmp_path / "d"))
    assert default_dump_dir() == str(tmp_path / "d")


# ---------------------------------------------------------------------------
# SLOConfig / burn-rate math (deterministic, fake telemetry)
# ---------------------------------------------------------------------------

class _FakeTelemetry:
    deployment = "fake"

    def __init__(self, samples):
        self._samples = samples

    def slo_samples(self):
        return self._samples


def test_slo_config_validation():
    with pytest.raises(ValueError):
        SLOConfig(objective=1.0)
    with pytest.raises(ValueError):
        SLOConfig(windows_s=())
    with pytest.raises(ValueError):
        SLOConfig(windows_s=(0.0,))
    with pytest.raises(ValueError):
        SLOConfig(ttft_ms=-1.0)
    with pytest.raises(ValueError):
        SLOConfig(min_samples=0)
    cfg = SLOConfig(ttft_ms=100.0, queue_wait_ms=5.0)
    assert cfg.objectives() == {"ttft": 100.0, "queue_wait": 5.0}


def test_burn_rate_math_and_windows():
    now = 1000.0
    # objective 0.9 -> 10% budget; 2 of 4 recent samples over target
    # -> violation rate 0.5 -> burn 5.0; the old sample falls out of
    # the 10 s window but still counts in the overall attainment
    cfg = SLOConfig(ttft_ms=100.0, objective=0.9, windows_s=(10.0,),
                    dump_on_breach=False)
    tel = _FakeTelemetry({"ttft": [
        (now - 60.0, 500.0),   # outside the window
        (now - 5.0, 50.0), (now - 4.0, 150.0),
        (now - 3.0, 50.0), (now - 2.0, 150.0)]})
    tr = SLOTracker(cfg, tel)
    snap = tr.snapshot(now=now)
    obj = snap["objectives"]["ttft"]
    assert obj["samples"] == 5 and obj["violations"] == 3
    assert obj["attainment"] == pytest.approx(0.4)
    win = obj["windows"]["10s"]
    assert win["samples"] == 4 and win["violations"] == 2
    assert win["burn_rate"] == pytest.approx(5.0)
    assert obj["burn_rate"] == pytest.approx(5.0)
    assert obj["breached"] and snap["breached"]
    # snapshot() is a pure read: no breach accounting happened
    assert snap["breaches"] == 0 and snap["dumps"] == []


def test_check_throttles_dumps_and_counts_breaches(tmp_path):
    now = 1000.0
    cfg = SLOConfig(e2e_ms=10.0, objective=0.5, windows_s=(30.0,),
                    check_interval_s=0.25, dump_dir=str(tmp_path))
    tel = _FakeTelemetry({"e2e": [(now - 1.0, 100.0)]})
    rec = FlightRecorder("fake", enabled=True)
    rec.record("step", dur_ms=1.0)
    tr = SLOTracker(cfg, tel, recorder=rec)
    assert rec.dump_dir == str(tmp_path)   # config redirects the dumps

    snap = tr.check(now=now)
    assert snap is not None and snap["breached"]
    assert tr.breaches == 1 and len(tr.dumps) == 1
    doc = load_dump(tr.dumps[0])
    assert doc["reason"] == "slo_breach_e2e"
    assert doc["context"]["objective"] == "e2e"
    assert doc["context"]["slo"]["objectives"]["e2e"]["breached"]

    # inside the throttle window -> no pass
    assert tr.check(now=now + 0.1) is None
    # still breached on the next pass: not a fresh transition,
    # no second dump
    snap = tr.check(now=now + 1.0)
    assert snap is not None and tr.breaches == 1
    assert len(tr.dumps) == 1


def test_recompile_storm_dump(tmp_path):
    cfg = SLOConfig(ttft_ms=1e9, check_interval_s=0.0,
                    dump_dir=str(tmp_path))
    tel = _FakeTelemetry({"ttft": []})
    rec = FlightRecorder("fake", enabled=True)
    tr = SLOTracker(cfg, tel, recorder=rec)
    tr.note_storm("serve.decode_step")
    tr.check(now=5.0)
    assert tr.breaches == 0          # a storm is not an SLO breach
    assert len(tr.dumps) == 1
    doc = load_dump(tr.dumps[0])
    assert doc["reason"] == "recompile_storm"
    assert doc["context"]["program"] == "serve.decode_step"


def test_max_dumps_caps_postmortems(tmp_path):
    cfg = SLOConfig(ttft_ms=1e9, check_interval_s=0.0,
                    dump_dir=str(tmp_path), max_dumps=2)
    tel = _FakeTelemetry({"ttft": []})
    tr = SLOTracker(cfg, tel,
                    recorder=FlightRecorder("fake", enabled=True))
    for i in range(5):
        tr.note_storm(f"p{i}")
        tr.check(now=float(i))
    assert len(tr.dumps) == 2


# ---------------------------------------------------------------------------
# end-to-end: engine breach -> dump -> CLI report (acceptance path)
# ---------------------------------------------------------------------------

def test_e2e_breach_dump_and_cli(tmp_path):
    # impossible targets: every request violates, burn explodes
    slo = SLOConfig(ttft_ms=1e-4, e2e_ms=1e-4, objective=0.5,
                    windows_s=(30.0,), check_interval_s=0.0,
                    dump_dir=str(tmp_path))
    dep = _build(slo=slo)
    outs, stats = _drive(dep, _prompts(4))
    assert all(isinstance(o, np.ndarray) for o in outs)

    blk = stats["slo"]
    assert blk is not None and blk["breached"]
    assert blk["breaches"] >= 1
    for name in ("ttft", "e2e"):
        obj = blk["objectives"][name]
        assert obj["burn_rate"] > 1.0
        assert obj["violations"] == obj["samples"] > 0
        assert obj["attainment"] == 0.0
    assert blk["config"]["targets_ms"] == {"ttft": 1e-4, "e2e": 1e-4}

    fr = stats["flightrec"]
    assert fr["enabled"] and fr["recorded"] > 0
    assert blk["dumps"] and blk["dumps"] == fr["dumps"]

    dump = blk["dumps"][0]
    doc = load_dump(dump)
    counts = doc["counts_by_kind"]
    # the journal holds the engine's decisions, not just the breach
    for kind in ("admit", "kv_reserve", "slo_breach"):
        assert counts.get(kind, 0) > 0, (kind, counts)
    assert counts.get("step", 0) + counts.get("first_token", 0) > 0
    assert doc["context"]["objective"] in ("ttft", "e2e")

    # the postmortem CLI must read the dump in a fresh process
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.tools.flightrec", "report",
         dump], capture_output=True, text=True, cwd=_REPO, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "SLO breach" in proc.stdout
    assert "<-- BREACHED" in proc.stdout


def test_engine_crash_writes_postmortem(tmp_path, monkeypatch):
    monkeypatch.setenv("RAYTPU_FLIGHTREC_DIR", str(tmp_path))
    dep = _build()

    async def main():
        inst = dep.func_or_class()
        try:
            # poison the pooled decode step so the engine loop dies
            # mid-step, with the request holding a slot
            await inst(_prompts(1)[0])   # healthy warmup request
            inst._pool_step = None
            with pytest.raises(Exception):
                await inst(_prompts(1, seed=1)[0])
        finally:
            inst.shutdown_engine()
        return inst._telemetry.flightrec

    rec = asyncio.run(main())
    crash_dumps = [p for p in rec.dumps if "engine_crash" in p]
    assert crash_dumps, rec.dumps
    doc = load_dump(crash_dumps[0])
    assert doc["reason"] == "engine_crash"
    assert doc["context"]["error"]
    assert doc["counts_by_kind"].get("engine_crash", 0) >= 1


# ---------------------------------------------------------------------------
# kv journal event shapes (kvscope forensics contract)
# ---------------------------------------------------------------------------

def test_kv_journal_events_carry_key_and_tenant():
    """Eviction/COW/re-prefill journal events must name WHAT was lost
    — the content key (first tokens + length) and the owning tenant —
    or eviction forensics cannot attribute cache thrash.  Regression
    guard on the event shapes postmortem tooling filters by."""
    from ray_tpu.serve.kv_pager import BlockPager

    rec = FlightRecorder("pager", enabled=True)
    bs = 4
    pager = BlockPager(num_blocks=5, block_size=bs, max_seq=16,
                       recorder=rec)

    # tenant A registers one prefix block, parks it in the LRU pool
    key_a = tuple(range(10, 10 + bs))
    pager.set_request(1, "trace-a", tenant="alpha")
    blocks = pager.allocate(1)
    assert pager.register_prefix(list(key_a), blocks) == 0
    pager.release(blocks)
    pager.set_request(None)

    # tenant B floods the pool: A's parked block is evicted
    pager.set_request(2, "trace-b", tenant="beta")
    flood = pager.allocate(4)
    assert pager.evictions == 1
    pager.release(flood)
    pager.set_request(None)

    ev = {e["kind"]: e for e in rec.snapshot()}
    evict = ev["kv_evict"]
    # the victim's owner, not the evictor, is named as tenant; the
    # evicting admission stays identifiable via req/trace
    assert evict["tenant"] == "alpha"
    assert evict["req"] == 2 and evict["trace"] == "trace-b"
    assert evict["key_prefix"] == list(key_a)[:8]
    assert evict["key_len"] == bs

    # A re-registers the same content: a kv_reprefill event books the
    # waste against the re-filling tenant with the same key tag
    pager.set_request(3, "trace-a2", tenant="alpha")
    blocks = pager.allocate(1)
    assert pager.register_prefix(list(key_a), blocks) == bs
    ev = {e["kind"]: e for e in rec.snapshot()}
    rp = ev["kv_reprefill"]
    assert rp["tokens"] == bs and rp["tenant"] == "alpha"
    assert rp["key_prefix"] == list(key_a)[:8]
    assert rp["key_len"] == bs

    # COW fork of the registered block carries the diverging key
    pager.release(blocks)
    _plen, matched = pager.match_prefix(list(key_a) + [99])
    assert matched
    fresh, src = pager.ensure_private(matched[0])
    assert src == matched[0]
    ev = {e["kind"]: e for e in rec.snapshot()}
    cow = ev["kv_cow"]
    assert cow["key_prefix"] == list(key_a)[:8]
    assert cow["key_len"] == bs
    assert cow["tenant"] == "alpha"
    pager.set_request(None)


# ---------------------------------------------------------------------------
# hot-path overhead guard
# ---------------------------------------------------------------------------

def test_recorder_overhead_under_5pct(monkeypatch, per_call_us):
    """The recorder must be cheap enough to leave on.  The engine is
    driven once with it on (its journal fills); the cost is then held
    to a budget per ``record()`` call, measured in isolation
    (``timeit``, min of repeats): the engine journals a handful of
    events a step, so 5% of even a 1 ms step leaves 10 us each, and a
    call must stay under 5 us (it is one counter increment and one
    deque append).  (This used to be a wall-clock A/B of two whole
    decode loops, which six xdist workers on shared cores decide, not
    the recorder; the name is kept for the history.)"""
    monkeypatch.setenv("RAYTPU_FLIGHTREC", "1")
    _, stats = _drive(_build(max_new_tokens=8), _prompts(4))
    assert stats["flightrec"]["enabled"]
    assert stats["flightrec"]["recorded"] >= 4 * 3   # admit/first/finish

    rec = FlightRecorder("budget", capacity=256)
    on = per_call_us(lambda: rec.record("step", dur_ms=1.0, active=2))
    assert rec.enabled and rec.recorded == per_call_us.calls
    assert on < 5.0, f"record() costs {on:.2f} us a call"
    monkeypatch.setenv("RAYTPU_FLIGHTREC", "0")
    quiet = FlightRecorder("budget_off", capacity=256)
    off = per_call_us(lambda: quiet.record("step", dur_ms=1.0, active=2))
    assert not quiet.enabled and quiet.recorded == 0    # early return
    assert off < 5.0


# ---------------------------------------------------------------------------
# CLI functions
# ---------------------------------------------------------------------------

def _synthetic_doc():
    return {
        "version": 1, "source": "eng", "reason": "slo_breach_ttft",
        "created": "2026-01-01T00:00:00", "uptime_s": 9.0,
        "events_recorded": 5, "events_retained": 5,
        "events_dropped": 0,
        "counts_by_kind": {"admit": 1, "shed": 1, "step": 3},
        "context": {"objective": "ttft", "slo": {
            "breaches": 1,
            "objectives": {"ttft": {
                "target_ms": 10.0, "attainment": 0.5,
                "burn_rate": 2.5, "violations": 1, "samples": 2,
                "breached": True}}}},
        "events": [
            {"seq": 1, "t_s": 0.1, "kind": "admit", "req": "r0"},
            {"seq": 2, "t_s": 0.2, "kind": "step", "dur_ms": 5.0},
            {"seq": 3, "t_s": 0.3, "kind": "step", "dur_ms": 7.0},
            {"seq": 4, "t_s": 0.4, "kind": "shed", "req": "r1",
             "reason": "queue full"},
            {"seq": 5, "t_s": 0.5, "kind": "step", "dur_ms": 6.0},
        ],
    }


def test_filter_events_kind_window_last():
    ev = _synthetic_doc()["events"]
    assert [e["seq"] for e in filter_events(ev, kinds=["step"])] \
        == [2, 3, 5]
    assert [e["seq"] for e in filter_events(ev, since=0.25,
                                            until=0.45)] == [3, 4]
    assert [e["seq"] for e in filter_events(ev, kinds=["step"],
                                            last=1)] == [5]


def test_report_lines_summarize_breach():
    text = "\n".join(report_lines(_synthetic_doc()))
    assert "slo_breach_ttft" in text
    assert "events by kind: admit=1, shed=1, step=3" in text
    assert "step dur_ms: n=3" in text
    assert "<-- BREACHED" in text
    assert "last sheds:" in text and "queue full" in text


def test_report_lines_render_fleet_routing_table():
    doc = _synthetic_doc()
    doc["events"] = doc["events"] + [
        {"seq": 6, "t_s": 0.6, "kind": "route", "req": 0,
         "replica": "fleet/r0", "policy": "prefix_affinity",
         "tenant": "interactive", "matched_blocks": 3,
         "outstanding": 0},
        {"seq": 7, "t_s": 0.7, "kind": "route", "req": 1,
         "replica": "fleet/r1", "policy": "p2c", "tenant": "batch",
         "matched_blocks": 0, "outstanding": 1},
        {"seq": 8, "t_s": 0.8, "kind": "route", "req": 2,
         "replica": "fleet/r0", "policy": "prefix_affinity",
         "tenant": "batch", "matched_blocks": 2, "outstanding": 1},
        {"seq": 9, "t_s": 0.9, "kind": "scale_up", "n_before": 2,
         "n_after": 3, "reason": "burn_rate", "signal": 4.2},
        {"seq": 10, "t_s": 1.0, "kind": "scale_down", "n_before": 3,
         "n_after": 2, "reason": "idle", "signal": 31.0,
         "replica": "fleet/r2"},
        {"seq": 11, "t_s": 1.1, "kind": "drain", "replica": "fleet/r2",
         "ok": True, "blocks_in_use": 0, "drained_requests": 0},
    ]
    text = "\n".join(report_lines(doc))
    assert "routing table (route events by replica):" in text
    # per-replica aggregation: r0 got 2 prefix-affinity routes with
    # 3+2 matched blocks across both tenants; r1 one p2c fallback
    assert "fleet/r0  2  2  0  0  5  batch,interactive" in text
    assert "fleet/r1  1  0  1  0  0  batch" in text
    assert "last scale-ups:" in text and '"reason": "burn_rate"' in text
    assert "last scale-downs:" in text and '"reason": "idle"' in text
    assert "last drains:" in text and '"blocks_in_use": 0' in text


def test_trace_events_merge_and_lane():
    doc = _synthetic_doc()
    base = [{"ph": "X", "name": "engine step", "pid": 1}]
    ev = trace_events(doc, merge=base)
    assert ev[0] == base[0]              # merged lane keeps originals
    instants = [e for e in ev if e.get("ph") == "i"]
    assert len(instants) == 5
    assert {e["name"] for e in instants} == {"admit", "step", "shed"}
    assert all(e["cat"] == "flightrec" for e in instants)


def test_sweepjson_records_shape():
    recs = sweepjson_records(_synthetic_doc())
    by_name = {r["metric"]: r for r in recs}
    assert by_name["flightrec_events_retained"]["value"] == 5
    assert by_name["flightrec_shed_events"]["value"] == 1
    assert by_name["flightrec_step_p95_ms"]["unit"] == "ms"
    assert by_name["flightrec_ttft_burn_rate"]["value"] == 2.5
    assert by_name["flightrec_ttft_slo_attainment"]["value"] == 0.5
    # every record is perfledger-ingestable: metric + numeric value
    from ray_tpu.tools.perfledger import extract_metrics
    for r in recs:
        m = extract_metrics(r)
        assert list(m) == [r["metric"]]
    # direction: attainment counts as higher-is-better despite "ttft"
    m = extract_metrics(by_name["flightrec_ttft_slo_attainment"])
    assert m["flightrec_ttft_slo_attainment"]["higher_is_better"]


def test_cli_main_subcommands(tmp_path):
    rec = FlightRecorder("cli", enabled=True)
    rec.dump_dir = str(tmp_path)
    rec.record("admit", req="r0")
    rec.record("step", dur_ms=3.0)
    dump = rec.dump(reason="manual")

    assert flightrec_main(["report", dump]) == 0
    assert flightrec_main(["events", dump, "--kind", "step"]) == 0
    assert flightrec_main(["sweepjson", dump]) == 0
    out = str(tmp_path / "trace.json")
    assert flightrec_main(["trace", dump, "-o", out]) == 0
    with open(out) as f:
        assert any(e.get("ph") == "i" for e in json.load(f))
    # unreadable dump -> exit 2, not a traceback
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert flightrec_main(["report", str(bad)]) == 2
