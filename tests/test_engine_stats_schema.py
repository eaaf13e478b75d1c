"""Golden-schema guard for ``engine_stats()``.

Dashboards, ``bench --traffic``, sweep records, the SLO admission
policy, and the postmortem tooling all pattern-match this dict; a
renamed or dropped key breaks them silently.  This test pins the
top-level key set and the shapes of the ``slo`` / ``programs`` /
``spec`` / ``flightrec`` blocks across the engine matrix: dense and
paged KV, speculative decoding on and off, and the mesh-sharded
engine on the 8-virtual-device CPU mesh.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.serve.llm import SpecConfig, build_llm_deployment  # noqa: E402
from ray_tpu.serve.slo import SLOConfig  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}

#: every key engine_stats() promises, regardless of configuration
TOP_KEYS = {
    "deployment", "uptime_s", "requests", "ttft_ms", "queue_wait_ms",
    "request_latency_ms", "inter_token_ms", "engine_steps",
    "tokens_generated", "tokens_per_sec", "slot_utilization",
    "max_active_slots", "max_slots", "prefill_buckets",
    "prefill_compiles", "program_compiles", "rejections_by_reason",
    "kv_cache", "kv_scope", "kv_tier", "recurrent", "spec", "slo",
    "flightrec",
    "programs", "latency_anatomy", "prefill_chunks", "role", "handoff",
    "health", "phases", "setup",
}

#: leaf phases every engine that ran a request has been through
#: (_private/scopes.py ENGINE_PHASES holds the whole list)
PHASES_ALWAYS = {"step", "loop", "admit", "rng_split",
                 "prefill_dispatch", "prefill_fence", "hooks", "yield"}

HEALTH_KEYS = {"enabled", "state", "suspect_ms", "dead_ms", "stall_ms",
               "heartbeats", "heartbeat_age_ms", "idle", "transitions",
               "suspect_count", "dead_count", "recoveries", "stalls",
               "time_to_detect_ms", "transition_log"}

KV_SCOPE_KEYS = {"enabled", "occupancy", "forensics",
                 "blocks_by_tenant", "hbm_ledger"}

KV_OCCUPANCY_KEYS = {"ring_capacity", "samples", "last",
                     "occupancy_ratio", "occupancy_p95",
                     "fragmentation", "ring"}

KV_FORENSICS_KEYS = {"keys_evicted", "keys_tracked", "keys_forgotten",
                     "reprefill_events", "reprefill_waste_tokens",
                     "reprefill_waste_frac", "prefill_tokens",
                     "tier_hits", "tokens_restored",
                     "waste_by_tenant", "top_keys"}

KV_TIER_KEYS = {"enabled", "bytes_budget", "bytes_resident", "entries",
                "hits", "misses", "hit_rate", "saves", "evictions",
                "tokens_restored", "h2d_ms", "d2h_ms"}

ANATOMY_KEYS = {"requests", "itl_ms", "tpot_ms", "ttft_ms",
                "critical_path", "by_tenant"}

CRITICAL_PATH_KEYS = {"e2e_ms", "router_wait_ms", "queue_wait_ms",
                      "requeue_ms", "kv_fetch_ms", "prefill_ms",
                      "prefill_wait_ms", "handoff_ms",
                      "inter_token_ms", "spec_rollback_ms"}

HANDOFF_KEYS = {"handoffs_out", "handoffs_in", "blocks_moved",
                "fast_path", "staged", "requeues"}

PREFILL_CHUNK_KEYS = {"requests", "chunks", "tokens",
                      "max_chunks_per_request"}

SUMMARY_KEYS = {"count", "mean", "p50", "p95", "p99", "max"}

SPEC_KEYS = {"proposed", "accepted", "rejected", "rounds",
             "accept_rate", "accept_rate_per_request"}

FLIGHTREC_KEYS = {"enabled", "capacity", "recorded", "retained",
                  "dropped", "dumps"}

SLO_OBJECTIVE_KEYS = {"target_ms", "samples", "violations",
                      "attainment", "burn_rate", "breached", "windows"}

PROGRAM_KEYS = {"compile_events", "compile_seconds", "harvest_seconds",
                "invokes",
                "invoke_ms", "xla_flops", "bytes_accessed",
                "arithmetic_intensity", "peak_hbm_bytes",
                "recompile_storm", "recompile_storms_total", "mfu"}


def _mesh():
    from ray_tpu.parallel import MeshSpec, fake_mesh

    return fake_mesh(8, MeshSpec(data=4, tensor=2))


def _stats(kv_layout, spec, mesh):
    # the process-wide program registry counts compiles per program
    # name over a minute: the engines other tests built on this worker
    # must not trip its recompile-storm alarm (a postmortem dump) here
    from ray_tpu._private.device_stats import reset_registry

    reset_registry()
    # generous targets: the SLO block must take its well-behaved
    # (unbreached) shape, not just the breach shape test_flightrec pins
    slo = SLOConfig(ttft_ms=60_000.0, e2e_ms=120_000.0,
                    queue_wait_ms=60_000.0)
    dep = build_llm_deployment(
        "gpt2", "nano", scheduler="continuous", kv_layout=kv_layout,
        kv_block_size=16, prefill_bucket=16, max_slots=2,
        max_new_tokens=3, temperature=0.0, slo=slo,
        spec_decode=spec, mesh=mesh, config_overrides=_OVR)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, 50, size=rng.randint(8, 14))
               .astype(np.int32) for _ in range(2)]

    async def main():
        inst = dep.func_or_class()
        try:
            await asyncio.gather(*[inst(p) for p in prompts])
            return inst.engine_stats()
        finally:
            inst.shutdown_engine()

    return asyncio.run(main())


@pytest.mark.parametrize("kv_layout,spec,sharded", [
    ("dense", None, False),
    ("paged", None, False),
    ("dense", SpecConfig(draft="ngram", k=2), False),
    ("paged", SpecConfig(draft="ngram", k=2), False),
    ("paged", None, True),
    ("paged", SpecConfig(draft="ngram", k=2), True),
], ids=["dense", "paged", "dense-spec", "paged-spec", "paged-mesh",
        "paged-spec-mesh"])
def test_engine_stats_schema(kv_layout, spec, sharded):
    stats = _stats(kv_layout, spec, _mesh() if sharded else None)

    missing = TOP_KEYS - set(stats)
    assert not missing, f"engine_stats() lost keys: {missing}"

    # requests sub-dict is a stable contract of its own
    for k in ("enqueued", "admitted", "finished", "rejected", "errors",
              "active", "queued"):
        assert k in stats["requests"], k

    # kv_cache: a pager block iff paged
    if kv_layout == "paged":
        assert isinstance(stats["kv_cache"], dict)
        assert "prefix_hit_rate" in stats["kv_cache"]
    else:
        assert stats["kv_cache"] is None

    # kv_scope: same shape for both layouts — paged engines report the
    # live kvscope block (occupancy ring sampled per wave, HBM
    # ledger), dense engines the stable zero-shaped block, so
    # dashboards and the kvscope CLI never branch on layout
    ks = stats["kv_scope"]
    assert set(ks) == KV_SCOPE_KEYS
    assert set(ks["occupancy"]) == KV_OCCUPANCY_KEYS
    assert set(ks["forensics"]) == KV_FORENSICS_KEYS
    assert set(ks["hbm_ledger"]) == {"per_chip", "min_headroom_bytes"}
    if kv_layout == "paged":
        assert ks["enabled"] is True
        assert ks["occupancy"]["samples"] > 0
        assert len(ks["occupancy"]["ring"]) == \
            ks["occupancy"]["samples"]
        assert len(ks["hbm_ledger"]["per_chip"]) >= 1
        for chip in ks["hbm_ledger"]["per_chip"]:
            assert chip["kv_pool_bytes"] > 0
    else:
        assert ks["enabled"] is False
        assert ks["occupancy"]["samples"] == 0
        assert ks["hbm_ledger"]["per_chip"] == []

    # kv_tier: same shape regardless of layout — no host tier is
    # configured anywhere in this matrix, so every engine (dense AND
    # paged) reports the zero-shaped disabled block; dashboards never
    # branch on whether a tier exists
    kt = stats["kv_tier"]
    assert set(kt) == KV_TIER_KEYS
    assert kt["enabled"] is False
    assert kt["hits"] == 0 and kt["misses"] == 0
    assert kt["tokens_restored"] == 0
    assert kt["bytes_resident"] == 0 and kt["entries"] == 0

    # phases: {phase: [count, seconds]} of the scheduler loop, the
    # same names whatever the layout; "step" counts iterations and the
    # leaves' seconds add up to its
    from ray_tpu._private import scopes

    ph = stats["phases"]
    assert PHASES_ALWAYS <= set(ph) <= set(scopes.ENGINE_PHASES) | {
        scopes.STEP}
    for count, seconds in ph.values():
        assert isinstance(count, int) and count > 0
        assert isinstance(seconds, float) and seconds >= 0.0
    assert sum(v[1] for k, v in ph.items() if k != "step") \
        == pytest.approx(ph["step"][1], rel=1e-9)
    assert ("kv.reserve" in ph) == (kv_layout == "paged")
    assert ("spec_round" in ph) == (spec is not None)
    assert ("decode_fence" in ph) == (spec is None)

    # setup: the same table of the constructor's leaves, each entered
    # once (a model draft would enter "params" and "cache" twice)
    assert set(stats["setup"]) == set(scopes.SETUP_PHASES)
    for count, seconds in stats["setup"].values():
        assert count == 1
        assert isinstance(seconds, float) and seconds >= 0.0

    # spec block always present; counters move iff spec decoding ran
    assert set(stats["spec"]) == SPEC_KEYS
    if spec is not None:
        assert stats["spec"]["rounds"] > 0
        assert stats["spec"]["proposed"] >= stats["spec"]["accepted"]
    else:
        assert stats["spec"]["rounds"] == 0

    # slo block: configured here, so never None
    blk = stats["slo"]
    assert set(blk) == {"config", "objectives", "breached", "breaches",
                        "dumps"}
    assert set(blk["config"]) == {"objective", "windows_s",
                                  "burn_threshold", "targets_ms"}
    assert set(blk["objectives"]) == {"ttft", "e2e", "queue_wait"}
    for obj in blk["objectives"].values():
        assert set(obj) == SLO_OBJECTIVE_KEYS
        for win in obj["windows"].values():
            assert set(win) == {"samples", "violations", "attainment",
                                "burn_rate"}
    assert blk["breached"] is False      # targets are unreachable-slow
    assert blk["breaches"] == 0 and blk["dumps"] == []

    # tracebus latency anatomy: ITL/TPOT percentiles + the
    # critical-path decomposition, same shape across the whole matrix
    anatomy = stats["latency_anatomy"]
    assert set(anatomy) == ANATOMY_KEYS
    assert anatomy["requests"] == 2  # both requests finished ok
    assert set(anatomy["itl_ms"]) == SUMMARY_KEYS
    assert set(anatomy["tpot_ms"]) == SUMMARY_KEYS
    assert set(anatomy["ttft_ms"]) == SUMMARY_KEYS
    assert anatomy["ttft_ms"]["count"] == 2
    assert set(anatomy["critical_path"]) == CRITICAL_PATH_KEYS
    for comp in anatomy["critical_path"].values():
        assert set(comp) == SUMMARY_KEYS
    # 3 new tokens per request -> inter-token gaps were recorded
    assert anatomy["itl_ms"]["count"] > 0
    # components sum to e2e (the invariant critical-path attribution
    # rests on), checked at the mean since summaries are per-component
    cp = anatomy["critical_path"]
    comp_sum = sum(cp[k]["mean"] for k in CRITICAL_PATH_KEYS
                   if k != "e2e_ms")
    assert comp_sum == pytest.approx(cp["e2e_ms"]["mean"], rel=0.05)
    assert anatomy["by_tenant"] == {}  # no tenant tags in this run

    # disaggregation block: monolithic engines report role "both" and
    # the zero-shaped handoff counter dict — same keys a role-split
    # replica reports live, so fleet_stats pooling never branches
    assert stats["role"] == "both"
    assert set(stats["handoff"]) == HANDOFF_KEYS
    assert all(v == 0 for v in stats["handoff"].values())

    # healthwatch block: always present and identically shaped —
    # standalone engines (no fleet, hence no HealthMonitor attached)
    # report the zero-shaped disabled block, so dashboards and
    # incident tooling never branch on whether a monitor exists
    hb = stats["health"]
    assert set(hb) == HEALTH_KEYS
    assert hb["enabled"] is False
    assert hb["state"] == "healthy"
    assert hb["heartbeats"] == 0 and hb["transitions"] == 0
    assert hb["stalls"] == 0
    assert hb["time_to_detect_ms"] is None
    assert hb["transition_log"] == []

    # chunked-prefill counter block: always present, all-zero when
    # chunking is off (as here — short prompts, no chunk knob)
    assert set(stats["prefill_chunks"]) == PREFILL_CHUNK_KEYS
    assert stats["prefill_chunks"]["requests"] == 0
    assert stats["prefill_chunks"]["chunks"] == 0

    # flight recorder: always on by default, journaling this run
    fr = stats["flightrec"]
    assert set(fr) == FLIGHTREC_KEYS
    assert fr["enabled"] and fr["recorded"] > 0
    assert fr["retained"] <= fr["capacity"]

    # perf observatory: serve-namespace programs with the full block
    assert isinstance(stats["programs"], dict)
    for name, prog in stats["programs"].items():
        assert name.startswith("serve."), name
        assert PROGRAM_KEYS <= set(prog), (name, prog.keys())

    # mesh block present exactly when sharded
    if sharded:
        assert set(stats["mesh"]) == {"axes", "n_devices", "kv_shards",
                                      "devices"}
        assert stats["mesh"]["n_devices"] == 8
    else:
        assert "mesh" not in stats


def test_engine_stats_kv_tier_enabled_shape():
    """A paged engine WITH a host tier reports the identical key set,
    just with ``enabled: True`` and a live byte budget — the golden
    shape must not fork on configuration."""
    slo = SLOConfig(ttft_ms=60_000.0, e2e_ms=120_000.0,
                    queue_wait_ms=60_000.0)
    dep = build_llm_deployment(
        "gpt2", "nano", scheduler="continuous", kv_layout="paged",
        kv_block_size=16, prefill_bucket=16, max_slots=2,
        max_new_tokens=3, temperature=0.0, slo=slo,
        kv_host_tier_bytes=1 << 20, config_overrides=_OVR)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, 50, size=rng.randint(8, 14))
               .astype(np.int32) for _ in range(2)]

    async def main():
        inst = dep.func_or_class()
        try:
            await asyncio.gather(*[inst(p) for p in prompts])
            return inst.engine_stats()
        finally:
            inst.shutdown_engine()

    stats = asyncio.run(main())
    kt = stats["kv_tier"]
    assert set(kt) == KV_TIER_KEYS
    assert kt["enabled"] is True
    assert kt["bytes_budget"] == 1 << 20


def test_engine_stats_role_split_shape():
    """A prefill/decode role pair keeps the identical golden key set;
    only ``role`` and the ``handoff`` counters differ.  Handoff-parked
    requests must NOT count as finished on the prefill side — they
    retire with the dedicated handoff status — while the decode side
    owns the end-to-end record (handoff_ms in its critical path)."""
    slo = SLOConfig(ttft_ms=60_000.0, e2e_ms=120_000.0,
                    queue_wait_ms=60_000.0)
    kw = dict(scheduler="continuous", kv_layout="paged",
              kv_block_size=16, prefill_bucket=16, max_slots=2,
              max_new_tokens=3, temperature=0.0, slo=slo,
              config_overrides=_OVR)
    pre = build_llm_deployment("gpt2", "nano", role="prefill", **kw)
    dec = build_llm_deployment("gpt2", "nano", role="decode", **kw)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(2, 50, size=rng.randint(8, 14))
               .astype(np.int32) for _ in range(2)]

    async def main():
        p_inst = pre.func_or_class()
        d_inst = dec.func_or_class()
        try:
            pkgs = await asyncio.gather(*[p_inst(p) for p in prompts])
            await asyncio.gather(*[d_inst.admit_prefilled(pkg)
                                   for pkg in pkgs])
            return p_inst.engine_stats(), d_inst.engine_stats()
        finally:
            p_inst.shutdown_engine()
            d_inst.shutdown_engine()

    p_st, d_st = asyncio.run(main())
    for stats in (p_st, d_st):
        missing = TOP_KEYS - set(stats)
        assert not missing, f"engine_stats() lost keys: {missing}"
        assert set(stats["handoff"]) == HANDOFF_KEYS
        assert set(stats["health"]) == HEALTH_KEYS

    assert p_st["role"] == "prefill"
    assert p_st["handoff"]["handoffs_out"] == 2
    assert p_st["handoff"]["handoffs_in"] == 0
    # parked ≠ finished: the decode side owns the completion record
    assert p_st["requests"]["finished"] == 0
    assert p_st["latency_anatomy"]["requests"] == 0

    assert d_st["role"] == "decode"
    assert d_st["handoff"]["handoffs_in"] == 2
    assert d_st["handoff"]["handoffs_out"] == 0
    assert d_st["handoff"]["blocks_moved"] > 0
    assert d_st["requests"]["finished"] == 2
    anatomy = d_st["latency_anatomy"]
    assert anatomy["requests"] == 2
    assert set(anatomy["critical_path"]) == CRITICAL_PATH_KEYS
    assert anatomy["critical_path"]["handoff_ms"]["count"] == 2
    cp = anatomy["critical_path"]
    comp_sum = sum(cp[k]["mean"] for k in CRITICAL_PATH_KEYS
                   if k != "e2e_ms")
    assert comp_sum == pytest.approx(cp["e2e_ms"]["mean"], rel=0.05)
