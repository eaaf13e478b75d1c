"""kvscope — KV-cache & HBM memory observatory.

Covers the three tentpole concerns end to end: occupancy timelines
(the per-wave ring and its exact conservation invariant), eviction
forensics + re-prefill waste (exact accounting against an independent
shadow model of the pager, and per-tenant attribution through a real
churn workload), and the unified HBM ledger (headroom math + the
AdmissionPolicy gate).  Satellites ride along: the prefix_pool churn
traffic class (RNG stream isolation), perfledger direction, the
tracebus kv.reserve tuple extension, autopilot cache-thrash
attribution, the CLI, and the hot-path overhead guard.
"""

import asyncio
import json
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.serve.batching import AdmissionPolicy  # noqa: E402
from ray_tpu.serve.kv_pager import BlockPager  # noqa: E402
from ray_tpu.serve.kvscope import (KVScope, empty_kv_scope,
                                   hbm_ledger)  # noqa: E402
from ray_tpu.serve.traffic import (TenantSpec, TrafficGenerator,
                                   TrafficSpec, run_traffic)  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}


# ---------------------------------------------------------------------------
# KVScope unit: occupancy ring + fragmentation
# ---------------------------------------------------------------------------

def test_fragmentation_is_read_again_only_when_the_free_list_moved():
    """The pager hands `sample` its count of changes to the free list:
    a wave that finds it unchanged reuses the last figure (sorting a
    16,384-block list every wave was 1.3 ms of host time; PERF.md,
    PR 28); without a version every sample reads the list, as before."""
    from ray_tpu.serve.kv_pager import BlockPager

    scope = KVScope(num_blocks=10, block_size=4, enabled=True)
    scope.sample([1, 2, 3, 4], cached=0, version=7)        # one run
    scope.sample([1, 3, 5, 7], cached=0, version=7)        # not read
    scope.sample([1, 3, 5, 7], cached=0, version=8)        # read
    scope.sample([1, 2, 3, 4], cached=0)                   # always read
    assert [s["frag"] for s in scope.timeline()] == [0.0, 0.0, 0.75, 0.0]
    pager = BlockPager(1 + 8, 4, 8)
    v0 = pager._free_version
    blocks = pager.allocate(3)
    assert pager._free_version > v0
    v1 = pager._free_version
    pager.sample_occupancy()
    pager.sample_occupancy()
    assert pager._free_version == v1
    pager.release(blocks)
    assert pager._free_version > v1
    assert [s["frag"] for s in pager.scope.timeline()][:2] == [0.0, 0.0]


def test_occupancy_ring_conservation_invariant():
    scope = KVScope(num_blocks=10, block_size=4, enabled=True)
    # free ids exclude the null block and whatever is in use/parked
    scope.sample(free_ids=[3, 4, 5, 6], cached=2)   # 3 in use (+null)
    scope.sample(free_ids=[], cached=5)             # pool saturated
    scope.sample(free_ids=list(range(1, 10)), cached=0)  # idle
    for s in scope.timeline():
        assert s["free"] + s["cached"] + s["in_use"] == 10, s
        assert s["null"] == 1
    st = scope.stats(free=9, cached=0)
    assert st["occupancy"]["samples"] == 3
    assert st["occupancy"]["occupancy_ratio"] == 0.0
    assert len(st["occupancy"]["ring"]) == 3


def test_fragmentation_is_contiguous_run_deficit():
    scope = KVScope(num_blocks=16, block_size=4, enabled=True)
    assert scope._fragmentation([]) == 0.0
    assert scope._fragmentation([7]) == 0.0
    assert scope._fragmentation([3, 4, 5, 6]) == 0.0       # one run
    # runs of 2+2: longest 2 of 4 free -> deficit 0.5
    assert scope._fragmentation([1, 2, 9, 10]) == 0.5
    # fully shattered: longest run 1 of 4 -> 0.75
    assert scope._fragmentation([1, 4, 8, 12]) == 0.75
    # order must not matter (free list is LIFO, not sorted)
    assert scope._fragmentation([12, 1, 8, 4]) == 0.75


def test_ring_is_bounded():
    scope = KVScope(num_blocks=4, block_size=4, ring_capacity=8,
                    enabled=True)
    for _ in range(20):
        scope.sample([1, 2], cached=0)
    assert len(scope.timeline()) == 8


def test_kill_switch_disables_all_hooks(monkeypatch):
    monkeypatch.setenv("RAYTPU_KVSCOPE", "0")
    scope = KVScope(num_blocks=8, block_size=4)
    assert not scope.enabled
    scope.sample([1, 2], cached=0)
    scope.note_alloc([1], "t")
    assert scope.note_register((1, 2, 3, 4), "t") == 0
    assert scope.note_evict((1, 2, 3, 4)) is None
    st = scope.stats(free=7, cached=0)
    assert st["occupancy"]["samples"] == 0
    assert st["forensics"]["reprefill_waste_tokens"] == 0
    # explicit override beats the env (mirrors FlightRecorder)
    assert KVScope(8, 4, enabled=True).enabled


def test_empty_kv_scope_matches_live_shape():
    scope = KVScope(num_blocks=8, block_size=4, enabled=True)
    live = scope.stats(free=7, cached=0)
    live["hbm_ledger"] = hbm_ledger()
    empty = empty_kv_scope()
    assert set(empty) == set(live)
    assert set(empty["occupancy"]) == set(live["occupancy"])
    assert set(empty["forensics"]) == set(live["forensics"])
    assert set(empty["hbm_ledger"]) == set(live["hbm_ledger"])


# ---------------------------------------------------------------------------
# eviction forensics: exact accounting vs an independent shadow model
# ---------------------------------------------------------------------------

def test_exact_waste_accounting_against_shadow_model():
    """Drive a real BlockPager through three laps of a rotating key
    set that overflows the pool, while the test maintains its OWN
    model of residency (free count, FIFO park order, evicted set) —
    the pager's booked waste must equal the model's, per tenant."""
    bs = 4
    pager = BlockPager(num_blocks=4, block_size=bs, max_seq=8)
    keys = [tuple(range(100 * k, 100 * k + bs)) for k in range(5)]
    tenants = ["alpha", "beta", "alpha", "beta", "alpha"]

    free_count = 3              # num_blocks - null
    parked = []                 # (key) in park order == LRU order
    resident = set()
    evicted = set()
    expected = {}               # tenant -> waste tokens

    for lap in range(3):
        for key, tenant in zip(keys, tenants):
            pager.set_request(1, None, tenant=tenant)
            # shadow: allocation evicts the LRU parked key iff the
            # free list is dry
            if free_count > 0:
                free_count -= 1
            else:
                victim = parked.pop(0)
                resident.discard(victim)
                evicted.add(victim)
            blocks = pager.allocate(1)
            assert blocks is not None
            waste = pager.register_prefix(list(key), blocks)
            # shadow: a register of previously-evicted content books
            # exactly block_size tokens; anything else books nothing
            if key in resident:
                assert waste == 0
                # duplicate content: the fresh block stays
                # unregistered, so release returns it to the free list
                pager.release(blocks)
                free_count += 1
                pager.set_request(None)
                continue
            if key in evicted:
                assert waste == bs
                evicted.discard(key)
                expected[tenant] = expected.get(tenant, 0) + bs
            else:
                assert waste == 0
            resident.add(key)
            parked.append(key)
            pager.release(blocks)      # parks (registered)
            pager.set_request(None)

    st = pager.kv_scope_stats()
    fx = st["forensics"]
    assert fx["waste_by_tenant"] == expected
    assert fx["reprefill_waste_tokens"] == sum(expected.values())
    assert fx["reprefill_waste_tokens"] > 0
    assert fx["reprefill_events"] * bs == fx["reprefill_waste_tokens"]
    assert fx["keys_evicted"] == pager.evictions


def test_evicted_key_ledger_is_bounded():
    scope = KVScope(num_blocks=8, block_size=4, key_cap=3,
                    enabled=True)
    for k in range(5):
        key = (k, k, k, k)
        scope.note_register(key, "t")
        scope.note_evict(key)
    assert scope.keys_evicted == 5
    assert scope.keys_forgotten == 2
    assert len(scope._evicted) == 3
    # a forgotten key re-registering books nothing (it fell off the
    # bounded ledger — undercounting, never overcounting)
    assert scope.note_register((0, 0, 0, 0), "t") == 0
    assert scope.note_register((4, 4, 4, 4), "t") == 4


# ---------------------------------------------------------------------------
# hbm ledger + admission gate
# ---------------------------------------------------------------------------

def test_hbm_ledger_headroom_math():
    led = hbm_ledger(
        pool_bytes_per_chip=100,
        program_budget_bytes=50,
        device_stats=[
            # allocator view dominates
            {"id": 0, "platform": "tpu", "bytes_limit": 1000,
             "bytes_in_use": 400, "peak_bytes_in_use": 500},
            # static commitment dominates (allocator under-reports)
            {"id": 1, "platform": "tpu", "bytes_limit": 1000,
             "bytes_in_use": 10, "peak_bytes_in_use": 10},
            # CPU: no limit -> no measurable headroom
            {"id": 2, "platform": "cpu", "bytes_limit": None,
             "bytes_in_use": None, "peak_bytes_in_use": None},
        ])
    rows = {r["id"]: r for r in led["per_chip"]}
    assert rows[0]["headroom_bytes"] == 1000 - 400
    assert rows[1]["headroom_bytes"] == 1000 - 150
    assert rows[2]["headroom_bytes"] is None
    assert led["min_headroom_bytes"] == 600
    # no devices at all -> inert
    assert hbm_ledger()["min_headroom_bytes"] is None


def test_admission_policy_hbm_headroom_gate():
    pol = AdmissionPolicy(min_headroom_bytes=1 << 20)
    low = {"kv_scope": {"hbm_ledger": {"min_headroom_bytes": 1024}}}
    ok = {"kv_scope": {"hbm_ledger": {"min_headroom_bytes": 2 << 20}}}
    inert = {"kv_scope": {"hbm_ledger": {"min_headroom_bytes": None}}}
    # fires regardless of backlog: exhausted HBM does not heal by
    # admitting more work
    assert pol.decide(low, queue_depth=0) == "hbm_headroom"
    assert pol.decide(low, queue_depth=5) == "hbm_headroom"
    assert pol.decide(ok, queue_depth=0) is None
    # inert when no chip reports a limit (CPU, dense engines)
    assert pol.decide(inert, queue_depth=0) is None
    assert pol.decide({}, queue_depth=0) is None
    assert pol.describe()["min_headroom_bytes"] == 1 << 20
    # default policy: gate off
    assert AdmissionPolicy().decide(low, queue_depth=0) is None


# ---------------------------------------------------------------------------
# prefix_pool churn traffic class
# ---------------------------------------------------------------------------

def test_prefix_pool_validation():
    with pytest.raises(ValueError, match="prefix_pool must be >= 1"):
        TenantSpec("t", 1.0, prefix_pool=0)
    with pytest.raises(ValueError, match="mutually exclusive"):
        TenantSpec("t", 1.0, prefix_groups=(0,), prefix_pool=2)


def test_prefix_pool_rotation_is_deterministic():
    spec = TrafficSpec(num_requests=60, seed=5, num_prefix_groups=3,
                       p_shared=0.9, vocab=300,
                       tenants=(TenantSpec("churn", 0.5, prefix_pool=4),
                                TenantSpec("bg", 0.5)))
    a = TrafficGenerator(spec).requests()
    b = TrafficGenerator(spec).requests()
    assert all(x.group == y.group and np.array_equal(x.prompt, y.prompt)
               and x.arrival_s == y.arrival_s for x, y in zip(a, b))
    # pool requests get distinct negative group ids -(2 + pool_idx),
    # never colliding with spec groups (>= 0) or unique (-1)
    pool_groups = {r.group for r in a
                   if r.tenant == "churn" and r.group < -1}
    assert pool_groups == {-2, -3, -4, -5}
    # round-robin: the churn tenant walks its pool in order
    seq = [-r.group - 2 for r in a
           if r.tenant == "churn" and r.group < -1]
    assert seq[:8] == [(i % 4) for i in range(8)]


def test_prefix_pool_leaves_cotenant_rng_stream_untouched():
    """The churn pool draws from its own seeded stream: flipping one
    tenant's prefix_pool must not perturb any other tenant's prompts
    (and with no pool set at all, the generator is the legacy one)."""
    kw = dict(num_requests=50, seed=7, num_prefix_groups=3,
              p_shared=0.8, vocab=300)
    with_pool = TrafficGenerator(TrafficSpec(
        tenants=(TenantSpec("churn", 0.5, prefix_pool=3),
                 TenantSpec("bg", 0.5)), **kw)).requests()
    without = TrafficGenerator(TrafficSpec(
        tenants=(TenantSpec("churn", 0.5),
                 TenantSpec("bg", 0.5)), **kw)).requests()
    assert len(with_pool) == len(without)
    for x, y in zip(with_pool, without):
        assert x.tenant == y.tenant        # same share draws
        assert x.arrival_s == y.arrival_s  # same arrival process
        if x.tenant == "bg":               # co-tenant bit-identical
            assert x.group == y.group
            assert np.array_equal(x.prompt, y.prompt)


# ---------------------------------------------------------------------------
# end-to-end: seeded churn workload through a real paged engine
# ---------------------------------------------------------------------------

def _churn_spec(n=40):
    return TrafficSpec(
        num_requests=n, seed=3, rate_rps=200.0, num_prefix_groups=2,
        prefix_len=32, p_shared=0.95, tail_len_mean=4.0,
        tail_len_max=8, vocab=300,
        tenants=(TenantSpec("churn", 0.7, prefix_pool=6),
                 TenantSpec("bg", 0.3)))


def test_churn_traffic_books_waste_and_keeps_invariant():
    rep = run_traffic(_churn_spec(), preset="nano", kv_layout="paged",
                      kv_block_size=16, kv_num_blocks=12, max_slots=2,
                      max_new_tokens=4, prefill_bucket=16,
                      time_scale=0.0, config_overrides=_OVR)
    ks = rep["engine"]["kv_scope"]
    assert ks["enabled"]
    # conservation at EVERY ring sample: free + cached + in_use is
    # exactly the pool size (null included in in_use)
    ring = ks["occupancy"]["ring"]
    assert len(ring) > 0
    for s in ring:
        assert s["free"] + s["cached"] + s["in_use"] == 12, s
    # the bounded pool thrashes: evictions happened and the same
    # prefixes came back
    fx = ks["forensics"]
    assert fx["keys_evicted"] > 0
    assert fx["reprefill_events"] > 0
    assert fx["reprefill_waste_tokens"] == \
        fx["reprefill_events"] * 16
    assert sum(fx["waste_by_tenant"].values()) == \
        fx["reprefill_waste_tokens"]
    assert fx["waste_by_tenant"].get("churn", 0) > 0
    assert 0.0 < fx["reprefill_waste_frac"] <= 1.0
    assert fx["reprefill_waste_frac"] == pytest.approx(
        fx["reprefill_waste_tokens"] / fx["prefill_tokens"], abs=1e-4)
    # report headlines flatten for SWEEPJSON/bench
    assert rep["kv_occupancy_p95"] == \
        ks["occupancy"]["occupancy_p95"] > 0
    assert rep["reprefill_waste_frac"] == fx["reprefill_waste_frac"]
    # top offender rows carry the key identity forensics render
    assert fx["top_keys"] and all(
        set(r) == {"key_prefix", "key_len", "tokens"}
        for r in fx["top_keys"])


def test_churn_journal_replay_matches_per_tenant_waste():
    """Independent per-tenant accounting from the flight recorder's
    journal: every kv_reprefill event must name content a prior
    kv_evict event recorded as lost, and the per-tenant sums must
    equal kvscope's waste_by_tenant exactly."""
    from ray_tpu.serve.llm import build_llm_deployment
    from ray_tpu.serve.traffic import drive

    dep = build_llm_deployment(
        "gpt2", "nano", scheduler="continuous", kv_layout="paged",
        kv_block_size=16, kv_num_blocks=12, prefill_bucket=16,
        max_slots=2, max_new_tokens=4, temperature=0.0,
        config_overrides=_OVR)
    requests = TrafficGenerator(_churn_spec()).requests()

    async def main():
        inst = dep.func_or_class()
        try:
            await drive(inst, requests, time_scale=0.0)
            return (inst.engine_stats(),
                    inst._telemetry.flightrec.snapshot())
        finally:
            inst.shutdown_engine()

    stats, events = asyncio.run(main())
    fx = stats["kv_scope"]["forensics"]
    evicted = set()
    replayed = {}
    for e in events:
        ident = (tuple(e.get("key_prefix") or ()), e.get("key_len"))
        if e["kind"] == "kv_evict":
            evicted.add(ident)
        elif e["kind"] == "kv_reprefill":
            assert ident in evicted, e
            replayed[e["tenant"]] = \
                replayed.get(e["tenant"], 0) + e["tokens"]
    assert replayed, "churn workload produced no re-prefill events"
    assert replayed == fx["waste_by_tenant"]
    assert sum(replayed.values()) == fx["reprefill_waste_tokens"]


def test_churn_journal_replay_tier_round_trip_books_zero_waste():
    """Same replay discipline with a host tier attached: every
    ``kv_fetch`` event names content a prior ``kv_evict`` recorded as
    lost, and an evict→fetch→register round-trip books ZERO re-prefill
    waste — while a restored key stays resident, no ``kv_reprefill``
    event may name it.  The forensics mirror must agree with the
    journal exactly (tier_hits == fetch events, tokens_restored ==
    their token sum)."""
    from ray_tpu.serve.llm import build_llm_deployment
    from ray_tpu.serve.traffic import drive

    dep = build_llm_deployment(
        "gpt2", "nano", scheduler="continuous", kv_layout="paged",
        kv_block_size=16, kv_num_blocks=12, prefill_bucket=16,
        max_slots=2, max_new_tokens=4, temperature=0.0,
        kv_host_tier_bytes=1 << 26, config_overrides=_OVR)
    requests = TrafficGenerator(_churn_spec()).requests()

    async def main():
        inst = dep.func_or_class()
        try:
            await drive(inst, requests, time_scale=0.0)
            return (inst.engine_stats(),
                    inst._telemetry.flightrec.snapshot())
        finally:
            inst.shutdown_engine()

    stats, events = asyncio.run(main())
    fx = stats["kv_scope"]["forensics"]
    evicted = set()
    restored_resident = set()
    fetches = 0
    fetched_tokens = 0
    for e in events:
        ident = (tuple(e.get("key_prefix") or ()), e.get("key_len"))
        if e["kind"] == "kv_evict":
            evicted.add(ident)
            restored_resident.discard(ident)
        elif e["kind"] == "kv_fetch":
            # a fetch can only restore content a prior evict lost
            assert ident in evicted, e
            assert e["bytes"] > 0 and e["tokens"] == 16, e
            restored_resident.add(ident)
            fetches += 1
            fetched_tokens += e["tokens"]
        elif e["kind"] == "kv_reprefill":
            # the round-trip invariant: registering a tier-restored
            # key must never book waste
            assert ident not in restored_resident, e
    assert fetches > 0, "tier never restored — workload did not churn"
    assert fx["tier_hits"] == fetches
    assert fx["tokens_restored"] == fetched_tokens
    kt = stats["kv_tier"]
    assert kt["enabled"] and kt["hits"] == fetches
    assert kt["tokens_restored"] == fetched_tokens


# ---------------------------------------------------------------------------
# autopilot attribution: cache-thrash clause
# ---------------------------------------------------------------------------

def test_autopilot_cites_cache_thrash_when_it_dominates():
    from ray_tpu.tools.autopilot.attribution import attribute

    dev = {"ridge_flops_per_byte": 1.0, "peak_flops_per_chip": 1.0,
           "peak_hbm_bytes_per_sec": 1.0}
    thrash = {"forensics": {"reprefill_waste_frac": 0.42,
                            "reprefill_waste_tokens": 8400}}
    rep = attribute({}, device=dev, kv_scope=thrash)
    assert "serving is cache-thrash-bound: 42% of prefill tokens " \
           "re-filled previously-resident prefixes" in rep["summary"]
    assert rep["kv_scope"] is thrash
    # below threshold: no clause
    calm = {"forensics": {"reprefill_waste_frac": 0.02,
                          "reprefill_waste_tokens": 40}}
    rep = attribute({}, device=dev, kv_scope=calm)
    assert "cache-thrash" not in rep["summary"]
    # the fleet-pooled block is flat (no "forensics" nesting)
    rep = attribute({}, device=dev,
                    kv_scope={"reprefill_waste_frac": 0.5,
                              "reprefill_waste_tokens": 100})
    assert "cache-thrash-bound: 50%" in rep["summary"]


# ---------------------------------------------------------------------------
# perfledger direction
# ---------------------------------------------------------------------------

def test_perfledger_ingests_kvscope_fields_lower_is_better():
    from ray_tpu.tools.perfledger import _SWEEP_FIELDS, higher_is_better

    assert "kv_occupancy_p95" in _SWEEP_FIELDS
    assert "reprefill_waste_frac" in _SWEEP_FIELDS
    # pool pressure and cache thrash regress UPWARD
    assert higher_is_better("kv_occupancy_p95") is False
    assert higher_is_better("reprefill_waste_frac") is False
    assert higher_is_better("gpt2_traffic_kv_occupancy_p95") is False
    assert higher_is_better(
        "gpt2_traffic_reprefill_waste_frac") is False
    # existing directions untouched
    assert higher_is_better("ttft_slo_attainment") is True
    assert higher_is_better("prefix_hit_rate") is True


# ---------------------------------------------------------------------------
# tracebus: kv.reserve span tuple extension
# ---------------------------------------------------------------------------

def test_tracebus_kv_reserve_span_carries_eviction_fields():
    from ray_tpu.tools.tracebus import build_request_spans

    req = {"request": "r0", "trace_id": "t" * 8, "enqueue": 0.0,
           "engine_enqueue": 0.01, "admit": 0.05,
           "first_token": 0.08, "finish": 0.1,
           "kv_reserve": (0.02, 0.03, 3, 1, 2, 16)}
    spans = {s["name"]: s for s in build_request_spans(req)}
    kv = spans["kv.reserve"]
    assert kv["attrs"]["blocks"] == 3
    assert kv["attrs"]["hit_blocks"] == 1
    assert kv["attrs"]["evicted"] == 2
    assert kv["attrs"]["reprefill_waste_tokens"] == 16
    # legacy 4-tuple records still render (None-padded)
    req["kv_reserve"] = (0.02, 0.03, 3, 1)
    spans = {s["name"]: s for s in build_request_spans(req)}
    assert spans["kv.reserve"]["attrs"]["evicted"] is None
    assert spans["kv.reserve"]["attrs"]["reprefill_waste_tokens"] \
        is None


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _snapshot_doc():
    scope = KVScope(num_blocks=8, block_size=4, enabled=True)
    scope.sample([1, 2, 3], cached=2)
    scope.sample([1], cached=3)
    scope.note_register((1, 2, 3, 4), "alpha")
    scope.note_evict((1, 2, 3, 4))
    scope.note_register((1, 2, 3, 4), "alpha")
    blk = scope.stats(free=1, cached=3, prefill_tokens=64)
    blk["hbm_ledger"] = hbm_ledger(
        pool_bytes_per_chip=256, program_budget_bytes=64,
        device_stats=[{"id": 0, "platform": "tpu",
                       "bytes_limit": 4096, "bytes_in_use": 1024,
                       "peak_bytes_in_use": 2048}])
    return blk


def test_cli_report_timeline_export(tmp_path):
    from ray_tpu.tools.kvscope import main as kvscope_main

    snap = tmp_path / "snap.json"
    # dashboard-map form: {deployment: {"kv_scope": block}}
    snap.write_text(json.dumps({"llm": {"kv_scope": _snapshot_doc()}}))
    assert kvscope_main(["report", str(snap)]) == 0
    assert kvscope_main(["timeline", str(snap)]) == 0
    out = str(tmp_path / "trace.json")
    assert kvscope_main(["export", str(snap), "-o", out]) == 0
    with open(out) as f:
        events = json.load(f)
    counters = [e for e in events if e.get("ph") == "C"]
    assert counters, events
    names = {e["name"] for e in counters}
    assert names == {"kv blocks", "kv occupancy", "kv fragmentation"}
    blocks = [e for e in counters if e["name"] == "kv blocks"]
    # counter lanes conserve the pool too
    for e in blocks:
        assert e["args"]["in_use"] + e["args"]["cached"] \
            + e["args"]["free"] == 8
    # unreadable snapshot -> exit 2, not a traceback
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"requests": []}))
    assert kvscope_main(["report", str(bad)]) == 2


def test_cli_load_snapshot_accepts_all_forms(tmp_path):
    from ray_tpu.tools.kvscope import load_snapshot

    blk = _snapshot_doc()
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(blk))
    assert list(load_snapshot(str(bare))) == ["engine"]
    eng = tmp_path / "eng.json"
    eng.write_text(json.dumps({"deployment": "llm_gpt2_nano",
                               "kv_scope": blk}))
    assert list(load_snapshot(str(eng))) == ["llm_gpt2_nano"]
    dash = tmp_path / "dash.json"
    dash.write_text(json.dumps({"a": {"kv_scope": blk},
                                "b": {"error": "down"}}))
    assert list(load_snapshot(str(dash))) == ["a"]


# ---------------------------------------------------------------------------
# hot-path overhead guard (mirrors flightrec's)
# ---------------------------------------------------------------------------

def test_kvscope_overhead_under_5pct(monkeypatch):
    """kvscope must be cheap enough to leave on: min-of-repeats
    decode-loop wall time with the scope on stays within 5% of the
    same loop with RAYTPU_KVSCOPE=0 (hooks early-return)."""
    from ray_tpu.serve.llm import build_llm_deployment

    dep = build_llm_deployment(
        "gpt2", "nano", scheduler="continuous", kv_layout="paged",
        kv_block_size=16, prefill_bucket=16, max_slots=2,
        max_new_tokens=32, temperature=0.0, config_overrides=_OVR)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, 50, size=rng.randint(8, 14))
               .astype(np.int32) for _ in range(6)]

    def run_once(scope_on):
        monkeypatch.setenv("RAYTPU_KVSCOPE", "1" if scope_on else "0")

        async def main():
            inst = dep.func_or_class()
            try:
                await asyncio.gather(*[inst(p) for p in prompts])
            finally:
                inst.shutdown_engine()

        t0 = time.perf_counter()
        asyncio.run(main())
        return time.perf_counter() - t0

    run_once(True)                     # compile warmup (shared cache)
    # CPU-CI wall clocks are noisy at this scale, and noise can only
    # produce FALSE failures here (the hooks are strictly additive
    # work) — so take interleaved min-of-5 pairs and allow a couple
    # of fresh attempts before declaring the hooks expensive
    pairs = []
    for _ in range(3):
        off = min(run_once(False) for _ in range(5))
        on = min(run_once(True) for _ in range(5))
        if on <= off * 1.05:
            return
        pairs.append((on, off))
    raise AssertionError(f"kvscope hooks >5% over baseline: {pairs}")
