"""TPU tuning sweep over bench.py's timing harness (dev tool).

Usage:
  python sweep_tpu.py '[[32, {}], [32, {"remat_policy": "dots_nb"}]]'

Each entry is [batch_per_chip, {overrides}].  "max_seq"/"seq" and
"preset" overrides are routed to time_config's seq/preset parameters;
everything else is passed to gpt2_config (so per-variant knobs like
ce_impl / flash_resident / remat_policy A/B straight from the sweep
spec).  Reuses bench.time_config so the methodology (donation, mesh,
fence, per-chip batch and MFU normalization) stays identical to the
official bench.

Decode variants: {"mode": "decode", ...} routes the entry to
bench.time_decode instead — batch is the TOTAL decode batch,
"seq"/"prompt_len" sets the prompt length, "new_tokens" the generated
tokens; the SWEEPJSON record carries prefill_ttft_ms + decode_tok_s
plus an "engine" sub-dict of p50/p95 TTFT and inter-token percentiles
from engine_stats().  E.g.:

  python sweep_tpu.py '[[8, {"mode": "decode"}],
                        [16, {"mode": "decode", "flash_resident": "on"}]]'

{"mode": "decode_sharded", ...} tensor-parallelises the same decode
harness over "tensor" local devices (default: every local device) via
bench.decode_mesh — params committed under DECODE_RULES, the cache
inheriting their sharding — and adds decode_tok_s_chip + the tensor
degree so A/Bs of degree 1 vs 4 vs 8 come straight from the spec:

  python sweep_tpu.py '[[8, {"mode": "decode"}],
                        [8, {"mode": "decode_sharded", "tensor": 4}],
                        [8, {"mode": "decode_sharded", "tensor": 8}]]'

{"mode": "decode_spec", ...} runs speculative decoding on the
CONTINUOUS engine (bench.time_decode_spec): "spec_k" drafted tokens
per round, "spec_draft" ("aligned" = a draft with the target's own
weights, acceptance ~1.0; "ngram"; or "<family>:<preset>"), plus
"kv_layout"/"tensor".  The record carries spec_accept_rate and
target_dispatches_per_token, so spec on/off × k A/Bs come straight
from the spec:

  python sweep_tpu.py '[[8, {"mode": "decode"}],
                        [8, {"mode": "decode_spec", "spec_k": 2}],
                        [8, {"mode": "decode_spec", "spec_k": 4}],
                        [8, {"mode": "decode_spec", "spec_k": 8}]]'

Traffic variants: {"mode": "traffic", ...} drives the continuous serve
engine under seeded shared-prefix Poisson load (serve/traffic.py) —
batch is max_slots, "requests"/"kv_layout"/"prefix_len"/"p_shared"/
"rate_rps"/"block_size" tune the workload; the SWEEPJSON record
carries prefix_hit_rate + slo_attainment plus shed counts and latency
percentiles, so dense-vs-paged A/Bs come straight from the sweep spec.
Add "tensor": N to shard the engine (tensor-parallel weights + paged
KV pool split over N chips); the record then carries mesh axes and
tok_s_chip:

  python sweep_tpu.py '[[8, {"mode": "traffic", "kv_layout": "dense"}],
                        [8, {"mode": "traffic", "kv_layout": "paged"}],
                        [8, {"mode": "traffic", "kv_layout": "paged",
                             "tensor": 4}]]'

{"mode": "traffic_fleet", ...} drives a multi-replica router fleet
(prefix-affinity routing + per-tenant WFQ) over the same two-tenant
churn mix; {"mode": "traffic_disagg", "prefill_replicas": P,
"decode_replicas": D, ...} splits the fleet by role with
block-granular KV handoff (add "handoff_staged": true for the
D2H→H2D hop), surfacing handoff_ms_p99 + per-role occupancy — a
traffic_fleet record at equal chip count is the A/B control:

  python sweep_tpu.py '[[8, {"mode": "traffic_fleet", "replicas": 2}],
                        [8, {"mode": "traffic_disagg",
                             "prefill_replicas": 1,
                             "decode_replicas": 1}]]'

{"mode": "traffic_chaos", ...} is traffic_fleet with one replica
FROZEN mid-traffic by seeded fault injection ("freeze_replica", chaos
knobs in serve/chaos.py): healthwatch must mark it SUSPECT→DEAD and
the router must requeue and route around it.  The record surfaces
time_to_detect_ms (fault → DEAD transition; perfledger tracks it
lower-is-better) and requests_requeued_on_death next to the usual
latency fields — a chaos-free traffic_fleet record at equal config is
the A/B control:

  python sweep_tpu.py '[[8, {"mode": "traffic_fleet", "replicas": 2}],
                        [8, {"mode": "traffic_chaos", "replicas": 2,
                             "freeze_replica": 1}]]'

Output: for every variant one HUMAN line and one machine-readable JSON
line (prefixed SWEEPJSON so `grep ^SWEEPJSON | cut -c11-` recovers a
clean JSONL stream).  The first record is the graftcheck static-audit
summary for the current tree (docs/static-analysis.md) so sweep
numbers are traceable to a tree whose hot-path invariants held; pass
--no-audit to skip it.  Pass --autopilot to append one final record
attributing every program the sweep registered against the device
roofline (ray_tpu/tools/autopilot — the closed tuning loop's
"attribute" stage), so the ledger carries WHY alongside the numbers.
A variant that fails is recorded with a tag ("oom" for an
out-of-memory compile or run, otherwise the exception's type) and the
sweep goes on, so sweeps that straddle a failure boundary remain
analyzable after the fact.

One process runs the whole sweep and owns the chip throughout; like
bench.py it fails without a TPU unless the caller set JAX_PLATFORMS=cpu.
"""
import json
import sys

from bench import (decode_mesh, require_backend, time_config,
                   time_decode, time_decode_spec)


def _failure_tag(e: Exception) -> str:
    """Classify a variant failure: out-of-memory apart from the rest."""
    msg = str(e)
    if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
        return "oom"
    return type(e).__name__


def _graftcheck_record():
    """One SWEEPJSON record summarizing the static audit (the same
    report ``python -m ray_tpu.tools.graftcheck --format json`` emits),
    so every sweep log carries proof the hot-path invariants held for
    the exact tree that produced the numbers.  Never raises: an audit
    crash is recorded, not fatal to the sweep."""
    try:
        from ray_tpu.tools.graftcheck import run_repo_check

        report = run_repo_check()
        summary = dict(report["summary"])
        # per-rule counters for the concurrency/determinism/registry
        # passes, so a sweep log shows at a glance whether the tree
        # that produced the numbers carried any of the three v2
        # finding classes (0 on a clean tree — the counters prove the
        # rules RAN, rules_failed names them only when they fire)
        for rule in ("shared-state-race", "rng-discipline",
                     "contract-registry"):
            summary[rule.replace("-", "_")] = sum(
                1 for v in report["violations"] if v["rule"] == rule)
        return {"graftcheck": summary, "ok": report["ok"]}
    except Exception as e:  # noqa: BLE001 - sweep must survive
        return {"graftcheck": {"error": f"{type(e).__name__}: "
                               f"{str(e)[:200]}"}, "ok": False}


def _run_traffic_variant(max_slots, kw, out):
    """One {"mode": "traffic"} sweep entry → SWEEPJSON record with
    prefix_hit_rate + slo_attainment (the two fields a dense-vs-paged
    A/B compares) plus shed counts and client latency percentiles."""
    from ray_tpu.serve.batching import AdmissionPolicy
    from ray_tpu.serve.llm import SpecConfig
    from ray_tpu.serve.slo import SLOConfig
    from ray_tpu.serve.traffic import (TenantSpec, TrafficSpec,
                                       run_traffic)

    kv_layout = kw.pop("kv_layout", "paged")
    tensor = kw.pop("tensor", 1)
    spec_k = kw.pop("spec_k", 0)
    spec_draft = kw.pop("spec_draft", "aligned")
    ttft_slo_ms = kw.pop("ttft_slo_ms", None)
    e2e_slo_ms = kw.pop("e2e_slo_ms", None)
    # chunked streaming prefill A/B: `long_prompt_len` switches to the
    # two-tenant long-prompt mixture (interactive short tails + batch
    # tenant flooding with fixed long prompts); `prefill_chunk` is the
    # chunk size (None/0 = one-shot — the control arm on the SAME
    # seeded traffic)
    prefill_chunk = kw.pop("prefill_chunk", None) or None
    long_prompt_len = kw.pop("long_prompt_len", None)
    # tiered host-RAM KV cache A/B: byte budget for the pager's host
    # tier (None/0 = tier off — the control arm); `kv_num_blocks`
    # shrinks the HBM pool to force churn the tier can absorb
    kv_host_tier_bytes = kw.pop("kv_host_tier_bytes", None) or None
    kv_num_blocks = kw.pop("kv_num_blocks", None) or None
    tenants = ()
    if long_prompt_len:
        tenants = (
            TenantSpec("interactive", rate_share=3.0,
                       slo_class="interactive"),
            TenantSpec("batch", rate_share=1.0, slo_class="batch",
                       prompt_len=long_prompt_len),
        )
    mesh, n_chips = decode_mesh(tensor)
    spec = TrafficSpec(
        num_requests=kw.pop("requests", 64),
        seed=kw.pop("seed", 0),
        rate_rps=kw.pop("rate_rps", 32.0),
        num_prefix_groups=kw.pop("prefix_groups", 4),
        prefix_len=kw.pop("prefix_len", 256),
        p_shared=kw.pop("p_shared", 0.75),
        tail_len_mean=kw.pop("tail_len_mean", 32.0),
        tail_len_max=kw.pop("tail_len_max", 128),
        vocab=kw.pop("vocab", 50000),
        tenants=tenants)
    run_kw = {
        "preset": kw.pop("preset", "gpt2"),
        "kv_block_size": kw.pop("block_size", 16),
        "max_new_tokens": kw.pop("new_tokens", 64),
        "prefill_bucket": kw.pop("prefill_bucket", 128),
        "prefill_chunk_tokens": prefill_chunk,
        "kv_num_blocks": kv_num_blocks,
        "kv_host_tier_bytes": kv_host_tier_bytes,
        "time_scale": kw.pop("time_scale", 1.0),
        "latency_slo_ms": kw.pop("latency_slo_ms", 20000.0),
    }
    policy = AdmissionPolicy(
        max_queue_depth=kw.pop("max_queue_depth",
                               4 * spec.num_requests))
    # engine-side SLO tracker: explicit ttft_slo_ms/e2e_slo_ms knobs,
    # defaulting to the legacy client-side bound (TTFT at half of it)
    slo_cfg = SLOConfig(
        ttft_ms=ttft_slo_ms if ttft_slo_ms is not None
        else run_kw["latency_slo_ms"] / 2,
        e2e_ms=e2e_slo_ms if e2e_slo_ms is not None
        else run_kw["latency_slo_ms"])
    spec_cfg = None
    if spec_k > 0:
        draft = (f"gpt2:{run_kw['preset']}" if spec_draft == "aligned"
                 else spec_draft)
        spec_cfg = SpecConfig(draft=draft, k=spec_k)
    variant = {"mode": "traffic", "max_slots": max_slots,
               "kv_layout": kv_layout, "requests": spec.num_requests,
               "prefix_len": spec.prefix_len,
               "p_shared": spec.p_shared, "rate_rps": spec.rate_rps,
               "tensor": n_chips, "spec_k": spec_k,
               "preset": run_kw["preset"],
               # block_size/prefill_bucket are popped into run_kw above,
               # which used to leave them out of the variant identity —
               # a block-size A/B hashed into ONE ledger series and
               # compared 16 against 64 as if they were the same config
               "block_size": run_kw["kv_block_size"],
               "prefill_bucket": run_kw["prefill_bucket"],
               # chunk size is variant identity: a chunk-size A/B must
               # never hash into one ledger series
               "prefill_chunk_tokens": prefill_chunk,
               "long_prompt_len": long_prompt_len,
               # tier budget (and any pool shrink forcing churn) is
               # variant identity: tier-on/off must never hash into
               # one ledger series
               "kv_host_tier_bytes": kv_host_tier_bytes,
               "kv_num_blocks": kv_num_blocks,
               "overrides": kw}
    try:
        rep = run_traffic(spec, family="gpt2", kv_layout=kv_layout,
                          max_slots=max_slots, mesh=mesh,
                          admission_policy=policy, slo=slo_cfg,
                          spec_decode=spec_cfg,
                          config_overrides=kw or None, **run_kw)
        eng = rep["engine"]
        tok_s = eng["tokens_per_sec"]
        print(f"traffic slots={max_slots} layout={kv_layout} "
              f"chips={n_chips} "
              f"n={rep['offered']}: hit_rate={rep['prefix_hit_rate']} "
              f"slo={rep['slo_attainment']} shed={rep['shed']} "
              f"{tok_s:,.0f} tok/s", file=out,
              flush=True)
        slo_rep = rep.get("slo") or {}
        rec = {"sweep": variant,
               "prefix_hit_rate": rep["prefix_hit_rate"],
               "slo_attainment": rep["slo_attainment"],
               "ttft_slo_attainment":
                   (slo_rep.get("ttft") or {}).get("attainment"),
               "e2e_slo_attainment":
                   (slo_rep.get("e2e") or {}).get("attainment"),
               "spec_accept_rate": rep.get("spec_accept_rate"),
               "itl_ms_p50": rep.get("itl_ms_p50"),
               "itl_ms_p99": rep.get("itl_ms_p99"),
               "ttft_critical_path": rep.get("ttft_critical_path"),
               # per-tenant TTFT p99, top-level so perfledger lifts
               # them (None outside the long-prompt mixture)
               "interactive_ttft_ms_p99":
                   rep.get("interactive_ttft_ms_p99"),
               "batch_ttft_ms_p99": rep.get("batch_ttft_ms_p99"),
               # kvscope headlines, top-level for perfledger
               # (lower-is-better: pool pressure + cache thrash)
               "kv_occupancy_p95": rep.get("kv_occupancy_p95"),
               "reprefill_waste_frac":
                   rep.get("reprefill_waste_frac"),
               # host-tier headline (higher-is-better; 0.0 tier-off)
               "kv_tier_hit_rate": rep.get("kv_tier_hit_rate"),
               "completed": rep["completed"], "shed": rep["shed"],
               "latency_p50_ms": rep["latency_ms"]["p50"],
               "latency_p95_ms": rep["latency_ms"]["p95"],
               "engine": {
                   "tokens_per_sec": tok_s,
                   "tok_s_chip": round(tok_s / max(1, n_chips), 1),
                   "mesh": eng.get("mesh"),
                   "ttft_p50_ms": (eng["ttft_ms"] or {}).get("p50"),
                   "ttft_p95_ms": (eng["ttft_ms"] or {}).get("p95"),
                   "kv_cache": eng.get("kv_cache"),
                   "kv_tier": eng.get("kv_tier"),
                   "prefill_chunks": eng.get("prefill_chunks"),
                   "rejections_by_reason":
                       eng["rejections_by_reason"]}}
    except Exception as e:  # noqa: BLE001 - sweep must survive
        print(f"traffic slots={max_slots} layout={kv_layout} {kw}: "
              f"FAILED {type(e).__name__}: {str(e)[:160]}", file=out,
              flush=True)
        rec = {"sweep": variant, "failed": _failure_tag(e),
               "error": f"{type(e).__name__}: {str(e)[:300]}"}
    return rec


def _run_traffic_fleet_variant(max_slots, kw, out):
    """One {"mode": "traffic_fleet"} sweep entry → SWEEPJSON record.

    Drives a multi-replica router fleet (prefix-affinity routing +
    per-tenant WFQ) and surfaces the two fleet headline numbers at the
    record's top level — ``router_prefix_hit_rate`` and the flattened
    ``{tenant}_{obj}_slo_attainment`` fields — because perfledger's
    extract_metrics only lifts top-level sweep-record keys."""
    from ray_tpu.serve.slo import SLOConfig
    from ray_tpu.serve.traffic import (TenantSpec, TrafficSpec,
                                       run_traffic_fleet)

    replicas = kw.pop("replicas", 2)
    routing = kw.pop("routing", "prefix")
    wfq = kw.pop("wfq", True)
    ttft_slo_ms = kw.pop("ttft_slo_ms", None)
    e2e_slo_ms = kw.pop("e2e_slo_ms", None)
    latency_slo_ms = kw.pop("latency_slo_ms", 20000.0)
    if ttft_slo_ms is None:
        ttft_slo_ms = latency_slo_ms / 2
    if e2e_slo_ms is None:
        e2e_slo_ms = latency_slo_ms
    groups = kw.pop("prefix_groups", 4)
    # default tenant mix: latency-sensitive interactive tenant on the
    # first half of the prefix pools, throughput batch tenant (loose
    # e2e-only objective) on the second half
    lo = tuple(range(groups // 2)) or (0,)
    hi = tuple(range(groups // 2, groups)) or (0,)
    p_int = min(max(kw.pop("p_interactive", 0.5), 0.01), 0.99)
    tenants = (
        TenantSpec("interactive", rate_share=p_int,
                   slo_class="interactive", prefix_groups=lo,
                   ttft_slo_ms=ttft_slo_ms, e2e_slo_ms=e2e_slo_ms),
        TenantSpec("batch", rate_share=1.0 - p_int,
                   slo_class="batch", prefix_groups=hi,
                   e2e_slo_ms=2 * e2e_slo_ms),
    )
    spec = TrafficSpec(
        num_requests=kw.pop("requests", 64),
        seed=kw.pop("seed", 0),
        rate_rps=kw.pop("rate_rps", 32.0),
        num_prefix_groups=groups,
        prefix_len=kw.pop("prefix_len", 256),
        p_shared=kw.pop("p_shared", 0.75),
        tail_len_mean=kw.pop("tail_len_mean", 32.0),
        tail_len_max=kw.pop("tail_len_max", 128),
        vocab=kw.pop("vocab", 50000),
        tenants=tenants)
    # tiered host-RAM KV cache A/B (per-replica tier; see
    # _run_traffic_variant for the knob semantics)
    kv_host_tier_bytes = kw.pop("kv_host_tier_bytes", None) or None
    kv_num_blocks = kw.pop("kv_num_blocks", None) or None
    run_kw = {
        "preset": kw.pop("preset", "gpt2"),
        "kv_block_size": kw.pop("block_size", 16),
        "kv_num_blocks": kv_num_blocks,
        "kv_host_tier_bytes": kv_host_tier_bytes,
        "max_new_tokens": kw.pop("new_tokens", 64),
        "prefill_bucket": kw.pop("prefill_bucket", 128),
        "time_scale": kw.pop("time_scale", 1.0),
    }
    slo_cfg = SLOConfig(ttft_ms=ttft_slo_ms, e2e_ms=e2e_slo_ms)
    variant = {"mode": "traffic_fleet", "max_slots": max_slots,
               "replicas": replicas, "routing": routing, "wfq": wfq,
               "requests": spec.num_requests,
               "prefix_len": spec.prefix_len,
               "p_shared": spec.p_shared, "rate_rps": spec.rate_rps,
               "preset": run_kw["preset"],
               "kv_host_tier_bytes": kv_host_tier_bytes,
               "kv_num_blocks": kv_num_blocks,
               "overrides": kw}
    try:
        rep = run_traffic_fleet(spec, num_replicas=replicas,
                                family="gpt2", max_slots=max_slots,
                                routing=routing, wfq=wfq, slo=slo_cfg,
                                config_overrides=kw or None, **run_kw)
        print(f"traffic_fleet slots={max_slots} replicas={replicas} "
              f"routing={routing} wfq={wfq} n={rep['offered']}: "
              f"router_hit_rate={rep['router_prefix_hit_rate']} "
              f"shed={rep['shed']}", file=out, flush=True)
        rec = {"sweep": variant,
               "router_prefix_hit_rate":
                   rep["router_prefix_hit_rate"],
               "itl_ms_p50": rep.get("itl_ms_p50"),
               "itl_ms_p99": rep.get("itl_ms_p99"),
               "ttft_critical_path": rep.get("ttft_critical_path"),
               # fleet-pooled kvscope headlines, top-level for
               # perfledger (lower-is-better)
               "kv_occupancy_p95": rep.get("kv_occupancy_p95"),
               "reprefill_waste_frac":
                   rep.get("reprefill_waste_frac"),
               # fleet-pooled host-tier headline (higher-is-better)
               "kv_tier_hit_rate": rep.get("kv_tier_hit_rate"),
               "completed": rep["completed"], "shed": rep["shed"],
               "latency_p50_ms": rep["latency_ms"]["p50"],
               "latency_p95_ms": rep["latency_ms"]["p95"],
               "fleet": {
                   "num_replicas": rep["num_replicas"],
                   "routed_by_policy":
                       rep["fleet"]["router"]["routed_by_policy"],
                   "tenants": rep["tenants"]}}
        # flatten {tenant}_{obj}_slo_attainment to the top level so
        # perfledger picks them up as trend series
        rec.update(rep.get("tenant_slo_attainment") or {})
    except Exception as e:  # noqa: BLE001 - sweep must survive
        print(f"traffic_fleet slots={max_slots} replicas={replicas} "
              f"{kw}: FAILED {type(e).__name__}: {str(e)[:160]}",
              file=out, flush=True)
        rec = {"sweep": variant, "failed": _failure_tag(e),
               "error": f"{type(e).__name__}: {str(e)[:300]}"}
    return rec


def _run_traffic_disagg_variant(max_slots, kw, out):
    """One {"mode": "traffic_disagg"} sweep entry → SWEEPJSON record.

    Drives a role-split fleet — ``prefill_replicas`` prefill engines
    feeding ``decode_replicas`` decode engines over block-granular KV
    handoff — against the same two-tenant churn mix as traffic_fleet,
    so a traffic_fleet record at equal chip count is the A/B control.
    Surfaces ``handoff_ms_p99`` and the per-role occupancy headlines
    at the record's top level for perfledger."""
    from ray_tpu.serve.slo import SLOConfig
    from ray_tpu.serve.traffic import (TenantSpec, TrafficSpec,
                                       run_traffic_fleet)

    prefill_replicas = kw.pop("prefill_replicas", 1)
    decode_replicas = kw.pop("decode_replicas", 1)
    handoff_staged = bool(kw.pop("handoff_staged", False))
    prefill_overrides = kw.pop("prefill_overrides", None) or None
    decode_overrides = kw.pop("decode_overrides", None) or None
    routing = kw.pop("routing", "prefix")
    wfq = kw.pop("wfq", True)
    ttft_slo_ms = kw.pop("ttft_slo_ms", None)
    e2e_slo_ms = kw.pop("e2e_slo_ms", None)
    latency_slo_ms = kw.pop("latency_slo_ms", 20000.0)
    if ttft_slo_ms is None:
        ttft_slo_ms = latency_slo_ms / 2
    if e2e_slo_ms is None:
        e2e_slo_ms = latency_slo_ms
    groups = kw.pop("prefix_groups", 4)
    lo = tuple(range(groups // 2)) or (0,)
    hi = tuple(range(groups // 2, groups)) or (0,)
    p_int = min(max(kw.pop("p_interactive", 0.5), 0.01), 0.99)
    tenants = (
        TenantSpec("interactive", rate_share=p_int,
                   slo_class="interactive", prefix_groups=lo,
                   ttft_slo_ms=ttft_slo_ms, e2e_slo_ms=e2e_slo_ms),
        TenantSpec("batch", rate_share=1.0 - p_int,
                   slo_class="batch", prefix_groups=hi,
                   e2e_slo_ms=2 * e2e_slo_ms),
    )
    spec = TrafficSpec(
        num_requests=kw.pop("requests", 64),
        seed=kw.pop("seed", 0),
        rate_rps=kw.pop("rate_rps", 32.0),
        num_prefix_groups=groups,
        prefix_len=kw.pop("prefix_len", 256),
        p_shared=kw.pop("p_shared", 0.75),
        tail_len_mean=kw.pop("tail_len_mean", 32.0),
        tail_len_max=kw.pop("tail_len_max", 128),
        vocab=kw.pop("vocab", 50000),
        tenants=tenants)
    kv_host_tier_bytes = kw.pop("kv_host_tier_bytes", None) or None
    kv_num_blocks = kw.pop("kv_num_blocks", None) or None
    run_kw = {
        "preset": kw.pop("preset", "gpt2"),
        "kv_block_size": kw.pop("block_size", 16),
        "kv_num_blocks": kv_num_blocks,
        "kv_host_tier_bytes": kv_host_tier_bytes,
        "max_new_tokens": kw.pop("new_tokens", 64),
        "prefill_bucket": kw.pop("prefill_bucket", 128),
        "time_scale": kw.pop("time_scale", 1.0),
    }
    slo_cfg = SLOConfig(ttft_ms=ttft_slo_ms, e2e_ms=e2e_slo_ms)
    variant = {"mode": "traffic_disagg", "max_slots": max_slots,
               "prefill_replicas": prefill_replicas,
               "decode_replicas": decode_replicas,
               "handoff_staged": handoff_staged,
               "routing": routing, "wfq": wfq,
               "requests": spec.num_requests,
               "prefix_len": spec.prefix_len,
               "p_shared": spec.p_shared, "rate_rps": spec.rate_rps,
               "preset": run_kw["preset"],
               "kv_host_tier_bytes": kv_host_tier_bytes,
               "kv_num_blocks": kv_num_blocks,
               "overrides": kw}
    try:
        rep = run_traffic_fleet(
            spec, num_replicas=decode_replicas,
            num_prefill_replicas=prefill_replicas,
            num_decode_replicas=decode_replicas,
            prefill_engine_kw=prefill_overrides,
            decode_engine_kw=decode_overrides,
            handoff_staged=handoff_staged,
            family="gpt2", max_slots=max_slots,
            routing=routing, wfq=wfq, slo=slo_cfg,
            config_overrides=kw or None, **run_kw)
        hoff = rep.get("handoff") or {}
        print(f"traffic_disagg slots={max_slots} "
              f"p={prefill_replicas} d={decode_replicas} "
              f"staged={handoff_staged} n={rep['offered']}: "
              f"handoffs={hoff.get('handoffs_in')} "
              f"handoff_ms_p99={rep.get('handoff_ms_p99')} "
              f"shed={rep['shed']}", file=out, flush=True)
        rec = {"sweep": variant,
               "router_prefix_hit_rate":
                   rep["router_prefix_hit_rate"],
               "itl_ms_p50": rep.get("itl_ms_p50"),
               "itl_ms_p99": rep.get("itl_ms_p99"),
               "ttft_critical_path": rep.get("ttft_critical_path"),
               # handoff hop cost, top-level for perfledger
               # (lower-is-better)
               "handoff_ms_p99": rep.get("handoff_ms_p99"),
               "handoff": hoff,
               "kv_occupancy_p95": rep.get("kv_occupancy_p95"),
               "reprefill_waste_frac":
                   rep.get("reprefill_waste_frac"),
               "kv_tier_hit_rate": rep.get("kv_tier_hit_rate"),
               "completed": rep["completed"], "shed": rep["shed"],
               "latency_p50_ms": rep["latency_ms"]["p50"],
               "latency_p95_ms": rep["latency_ms"]["p95"],
               "fleet": {
                   "num_replicas": rep["num_replicas"],
                   "num_prefill_replicas":
                       rep.get("num_prefill_replicas"),
                   "num_decode_replicas":
                       rep.get("num_decode_replicas"),
                   "routed_by_policy":
                       rep["fleet"]["router"]["routed_by_policy"],
                   "tenants": rep["tenants"]}}
        # per-role occupancy headlines (prefill pools should run
        # near-empty; decode pools carry the steady-state residency)
        for key in ("prefill_kv_occupancy_mean",
                    "prefill_kv_occupancy_p95",
                    "decode_kv_occupancy_mean",
                    "decode_kv_occupancy_p95"):
            if rep.get(key) is not None:
                rec[key] = rep[key]
        rec.update(rep.get("tenant_slo_attainment") or {})
    except Exception as e:  # noqa: BLE001 - sweep must survive
        print(f"traffic_disagg slots={max_slots} "
              f"p={prefill_replicas} d={decode_replicas} {kw}: "
              f"FAILED {type(e).__name__}: {str(e)[:160]}",
              file=out, flush=True)
        rec = {"sweep": variant, "failed": _failure_tag(e),
               "error": f"{type(e).__name__}: {str(e)[:300]}"}
    return rec


def _run_traffic_chaos_variant(max_slots, kw, out):
    """One {"mode": "traffic_chaos"} sweep entry → SWEEPJSON record.

    The traffic_fleet mixture with one replica FROZEN mid-traffic by
    seeded fault injection (serve/chaos.py): healthwatch
    (serve/health.py) must transition it SUSPECT→DEAD, the router must
    route around it, and the record surfaces the detection headlines —
    ``time_to_detect_ms`` (fault instant → DEAD transition,
    lower-is-better in perfledger) and
    ``requests_requeued_on_death`` — next to the same latency/hit-rate
    fields as traffic_fleet, so the chaos-free record at equal config
    is the A/B control for the blip's cost."""
    from ray_tpu.serve.chaos import ChaosConfig
    from ray_tpu.serve.health import HealthConfig
    from ray_tpu.serve.slo import SLOConfig
    from ray_tpu.serve.traffic import (TenantSpec, TrafficSpec,
                                       run_traffic_fleet)

    replicas = kw.pop("replicas", 2)
    routing = kw.pop("routing", "prefix")
    freeze_replica = kw.pop("freeze_replica", replicas - 1)
    suspect_ms = kw.pop("suspect_ms", 40.0)
    dead_ms = kw.pop("dead_ms", 120.0)
    stall_ms = kw.pop("stall_ms", 80.0)
    freeze_waves = kw.pop("freeze_waves", 200)
    ttft_slo_ms = kw.pop("ttft_slo_ms", 10000.0)
    e2e_slo_ms = kw.pop("e2e_slo_ms", 20000.0)
    groups = kw.pop("prefix_groups", 4)
    lo = tuple(range(groups // 2)) or (0,)
    hi = tuple(range(groups // 2, groups)) or (0,)
    tenants = (
        TenantSpec("interactive", rate_share=0.5,
                   slo_class="interactive", prefix_groups=lo,
                   ttft_slo_ms=ttft_slo_ms, e2e_slo_ms=e2e_slo_ms),
        TenantSpec("batch", rate_share=0.5, slo_class="batch",
                   prefix_groups=hi, e2e_slo_ms=2 * e2e_slo_ms),
    )
    spec = TrafficSpec(
        num_requests=kw.pop("requests", 64),
        seed=kw.pop("seed", 0),
        rate_rps=kw.pop("rate_rps", 32.0),
        num_prefix_groups=groups,
        prefix_len=kw.pop("prefix_len", 256),
        p_shared=kw.pop("p_shared", 0.75),
        tail_len_mean=kw.pop("tail_len_mean", 32.0),
        tail_len_max=kw.pop("tail_len_max", 128),
        vocab=kw.pop("vocab", 50000),
        tenants=tenants)
    health = HealthConfig(suspect_ms=suspect_ms, dead_ms=dead_ms,
                          stall_ms=stall_ms, probe_ms=5.0)
    chaos = ChaosConfig(seed=spec.seed,
                        freeze_replica=int(freeze_replica),
                        freeze_after_waves=2,
                        freeze_waves=int(freeze_waves),
                        freeze_poll_ms=5.0)
    run_kw = {
        "preset": kw.pop("preset", "gpt2"),
        "kv_block_size": kw.pop("block_size", 16),
        "kv_num_blocks": kw.pop("kv_num_blocks", None) or None,
        "max_new_tokens": kw.pop("new_tokens", 64),
        "prefill_bucket": kw.pop("prefill_bucket", 128),
        "time_scale": kw.pop("time_scale", 1.0),
    }
    variant = {"mode": "traffic_chaos", "max_slots": max_slots,
               "replicas": replicas, "routing": routing,
               "freeze_replica": int(freeze_replica),
               "suspect_ms": suspect_ms, "dead_ms": dead_ms,
               "stall_ms": stall_ms, "freeze_waves": int(freeze_waves),
               "requests": spec.num_requests,
               "prefix_len": spec.prefix_len,
               "rate_rps": spec.rate_rps,
               "preset": run_kw["preset"], "overrides": kw}
    try:
        rep = run_traffic_fleet(
            spec, num_replicas=replicas, family="gpt2",
            max_slots=max_slots, routing=routing,
            slo=SLOConfig(ttft_ms=ttft_slo_ms, e2e_ms=e2e_slo_ms),
            health=health, chaos=chaos,
            config_overrides=kw or None, **run_kw)
        print(f"traffic_chaos slots={max_slots} replicas={replicas} "
              f"frozen=r{freeze_replica} n={rep['offered']}: "
              f"time_to_detect_ms={rep['time_to_detect_ms']} "
              f"requeued={rep['requests_requeued_on_death']} "
              f"shed={rep['shed']}", file=out, flush=True)
        rec = {"sweep": variant,
               "time_to_detect_ms": rep.get("time_to_detect_ms"),
               "requests_requeued_on_death":
                   rep.get("requests_requeued_on_death"),
               "router_prefix_hit_rate":
                   rep["router_prefix_hit_rate"],
               "itl_ms_p50": rep.get("itl_ms_p50"),
               "itl_ms_p99": rep.get("itl_ms_p99"),
               "completed": rep["completed"], "shed": rep["shed"],
               "latency_p50_ms": rep["latency_ms"]["p50"],
               "latency_p95_ms": rep["latency_ms"]["p95"],
               "fleet": {
                   "num_replicas": rep["num_replicas"],
                   "health": rep["fleet"].get("health"),
                   "routed_by_policy":
                       rep["fleet"]["router"]["routed_by_policy"]}}
        rec.update(rep.get("tenant_slo_attainment") or {})
    except Exception as e:  # noqa: BLE001 - sweep must survive
        print(f"traffic_chaos slots={max_slots} replicas={replicas} "
              f"{kw}: FAILED {type(e).__name__}: {str(e)[:160]}",
              file=out, flush=True)
        rec = {"sweep": variant, "failed": _failure_tag(e),
               "error": f"{type(e).__name__}: {str(e)[:300]}"}
    return rec


def _autopilot_record():
    """One SWEEPJSON record attributing every program this sweep
    registered (compute- vs HBM-bound against the device ridge, ranked
    by headroom-weighted time share) — ``--autopilot`` appends it after
    the variant records so the attribution rides into the ledger with
    the numbers it explains.  Never raises."""
    try:
        from ray_tpu.tools.autopilot import attribute_registry

        return {"autopilot": attribute_registry()}
    except Exception as e:  # noqa: BLE001 - sweep must survive
        return {"autopilot": {"error": f"{type(e).__name__}: "
                              f"{str(e)[:200]}"}}


def run_sweep(configs, n_chips, n_steps=10, out=sys.stdout,
              audit=False, ledger=True, ledger_path=None,
              autopilot=False):
    """Run each [batch_per_chip, overrides] variant; returns the list of
    result records that were also emitted as SWEEPJSON lines.  With
    ``audit=True`` the first record is the graftcheck summary for the
    current tree (``python sweep_tpu.py`` turns this on; pass
    --no-audit to skip).  With ``autopilot=True`` (--autopilot) the
    LAST record is the roofline attribution of every program the sweep
    registered.  Unless ``ledger=False`` (--no-ledger), every
    record is also appended to BENCH_HISTORY.jsonl through
    ray_tpu/tools/perfledger so the sweep trajectory outlives the
    terminal — SWEEPJSON lines used to evaporate with the scrollback."""
    records = []
    if audit:
        rec = _graftcheck_record()
        print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
        records.append(rec)
    for batch_per_chip, kw in configs:
        kw = dict(kw)
        mode = kw.pop("mode", "train")
        if mode in ("decode", "decode_sharded"):
            prompt_len = kw.pop("prompt_len",
                                kw.pop("max_seq", kw.pop("seq", 128)))
            new_tokens = kw.pop("new_tokens", 64)
            preset = kw.pop("preset", "gpt2")
            tensor = kw.pop("tensor",
                            n_chips if mode == "decode_sharded" else 1)
            variant = {"mode": mode, "batch": batch_per_chip,
                       "prompt_len": prompt_len,
                       "new_tokens": new_tokens, "preset": preset,
                       "tensor": tensor, "overrides": kw}
            try:
                mesh, _ = decode_mesh(tensor)
                ttft_ms, tok_s, stats, chips = time_decode(
                    batch_per_chip, prompt_len=prompt_len,
                    new_tokens=new_tokens, preset=preset, mesh=mesh,
                    **kw)
                print(f"{mode} batch={batch_per_chip} "
                      f"prompt={prompt_len} new={new_tokens} "
                      f"chips={chips} {kw}: "
                      f"TTFT={ttft_ms:.2f}ms  {tok_s:,.0f} tok/s "
                      f"({tok_s / max(1, chips):,.0f} tok/s/chip)",
                      file=out, flush=True)

                def _r(v, nd=2):
                    return None if v is None else round(v, nd)

                rec = {"sweep": variant,
                       "prefill_ttft_ms": round(ttft_ms, 2),
                       "decode_tok_s": round(tok_s, 1),
                       "decode_tok_s_chip":
                           round(tok_s / max(1, chips), 1),
                       "chips": chips,
                       # percentiles from the serve engine_stats() path
                       "engine": {
                           "ttft_p50_ms": _r(stats["ttft_ms"]["p50"]),
                           "ttft_p95_ms": _r(stats["ttft_ms"]["p95"]),
                           "inter_token_p50_ms":
                               _r(stats["inter_token_ms"]["p50"], 3),
                           "inter_token_p95_ms":
                               _r(stats["inter_token_ms"]["p95"], 3),
                           "tokens_per_sec":
                               _r(stats["tokens_per_sec"], 1)}}
            except Exception as e:
                print(f"{mode} batch={batch_per_chip} "
                      f"prompt={prompt_len} {kw}: FAILED "
                      f"{type(e).__name__}: {str(e)[:160]}", file=out,
                      flush=True)
                rec = {"sweep": variant, "failed": _failure_tag(e),
                       "error": f"{type(e).__name__}: {str(e)[:300]}"}
            print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
            records.append(rec)
            continue
        if mode == "decode_spec":
            prompt_len = kw.pop("prompt_len", 128)
            new_tokens = kw.pop("new_tokens", 64)
            preset = kw.pop("preset", "gpt2")
            spec_k = kw.pop("spec_k", kw.pop("k", 4))
            spec_draft = kw.pop("spec_draft", "aligned")
            kv_layout = kw.pop("kv_layout", "dense")
            tensor = kw.pop("tensor", 1)
            variant = {"mode": mode, "batch": batch_per_chip,
                       "prompt_len": prompt_len,
                       "new_tokens": new_tokens, "preset": preset,
                       "spec_k": spec_k, "spec_draft": spec_draft,
                       "kv_layout": kv_layout, "tensor": tensor,
                       "overrides": kw}
            try:
                mesh, _ = decode_mesh(tensor)
                tok_s, stats, dpt, chips = time_decode_spec(
                    batch_per_chip, prompt_len=prompt_len,
                    new_tokens=new_tokens, preset=preset,
                    spec_k=spec_k, spec_draft=spec_draft,
                    kv_layout=kv_layout, mesh=mesh,
                    config_overrides=kw or None)
                spec = stats["spec"]
                print(f"{mode} batch={batch_per_chip} k={spec_k} "
                      f"draft={spec_draft} chips={chips}: "
                      f"{tok_s:,.0f} tok/s "
                      f"accept={spec['accept_rate']} "
                      f"dispatch/tok={dpt:.3f}", file=out, flush=True)
                rec = {"sweep": variant,
                       "decode_tok_s": round(tok_s, 1),
                       "decode_tok_s_chip":
                           round(tok_s / max(1, chips), 1),
                       "spec_accept_rate": spec["accept_rate"],
                       "target_dispatches_per_token": round(dpt, 4),
                       "chips": chips,
                       "engine": {"spec": spec}}
            except Exception as e:
                print(f"{mode} batch={batch_per_chip} k={spec_k} "
                      f"{kw}: FAILED {type(e).__name__}: "
                      f"{str(e)[:160]}", file=out, flush=True)
                rec = {"sweep": variant, "failed": _failure_tag(e),
                       "error": f"{type(e).__name__}: {str(e)[:300]}"}
            print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
            records.append(rec)
            continue
        if mode == "traffic":
            rec = _run_traffic_variant(batch_per_chip, kw, out)
            print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
            records.append(rec)
            continue
        if mode == "traffic_fleet":
            rec = _run_traffic_fleet_variant(batch_per_chip, kw, out)
            print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
            records.append(rec)
            continue
        if mode == "traffic_disagg":
            rec = _run_traffic_disagg_variant(batch_per_chip, kw, out)
            print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
            records.append(rec)
            continue
        if mode == "traffic_chaos":
            rec = _run_traffic_chaos_variant(batch_per_chip, kw, out)
            print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
            records.append(rec)
            continue
        seq = kw.pop("max_seq", kw.pop("seq", 1024))
        preset = kw.pop("preset", "gpt2")
        variant = {"batch_per_chip": batch_per_chip, "seq": seq,
                   "preset": preset, "overrides": kw}
        try:
            tok_s_chip, mfu, _, n, cost = time_config(
                batch_per_chip * n_chips, seq=seq, n_steps=n_steps,
                preset=preset, **kw)
            print(f"batch/chip={batch_per_chip} seq={seq} {kw}: "
                  f"{tok_s_chip:,.0f} tok/s/chip (x{n} chips)  "
                  f"MFU={mfu:.4f}", file=out, flush=True)
            rec = {"sweep": variant, "tok_s_chip": round(tok_s_chip, 1),
                   "mfu": round(mfu, 4), "chips": n,
                   # compiler-side numbers (bench.time_config AOT cost
                   # harvest): MFU from XLA's own FLOP count + peak HBM
                   "mfu_xla": (round(cost["mfu_xla"], 4)
                               if cost.get("mfu_xla") else None),
                   "xla_flops": cost.get("xla_flops"),
                   "peak_hbm_bytes": cost.get("peak_hbm_bytes")}
        except Exception as e:
            print(f"batch/chip={batch_per_chip} seq={seq} {kw}: FAILED "
                  f"{type(e).__name__}: {str(e)[:160]}", file=out,
                  flush=True)
            rec = {"sweep": variant, "failed": _failure_tag(e),
                   "error": f"{type(e).__name__}: {str(e)[:300]}"}
        print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
        records.append(rec)
    if autopilot:
        rec = _autopilot_record()
        print("SWEEPJSON " + json.dumps(rec), file=out, flush=True)
        records.append(rec)
    if ledger and records:
        try:
            from ray_tpu.tools import perfledger

            n = perfledger.append_records(records, source="sweep",
                                          path=ledger_path)
            print(f"sweep: {n} record(s) appended to "
                  f"{perfledger.history_path(ledger_path)}", file=out,
                  flush=True)
        except Exception as e:  # noqa: BLE001 - ledger is best-effort
            print(f"sweep: perf ledger append failed: {e!r}",
                  file=out, flush=True)
    return records


if __name__ == "__main__":
    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    require_backend()
    argv = [a for a in sys.argv[1:]
            if a not in ("--no-audit", "--no-ledger", "--autopilot")]
    n_chips = len(jax.devices())
    configs = json.loads(argv[0]) if argv else [
        [32, {}],
    ]
    run_sweep(configs, n_chips, audit="--no-audit" not in sys.argv,
              ledger="--no-ledger" not in sys.argv,
              autopilot="--autopilot" in sys.argv)
