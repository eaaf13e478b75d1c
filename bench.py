"""North-star benchmark: GPT-2-124M training throughput on TPU.

Measures full training steps (forward + backward + AdamW) of the GPT-2
flagship (ray_tpu/models/gpt2.py, pallas flash attention) on the local
chip(s) and prints ONE JSON line.

Baseline: the reference publishes no absolute GPT-2 tokens/s (SURVEY.md
§6; BASELINE.json "published": {}).  Its GPU north-star anchor (BASELINE
"GPU-parity throughput") is encoded as 40% MFU — a strong torch/DDP GPU
baseline for a 124M model — against this chip's peak bf16 FLOPs, so
vs_baseline = achieved_MFU / 0.40.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time


BASELINE_MFU = 0.40


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--chips", type=int, default=0,
                    help="run on a mesh of the first N local devices "
                         "(default: all of them); fails when fewer "
                         "are attached")
    ap.add_argument("--mesh", default="data",
                    choices=["data", "fsdp", "data_fsdp", "tensor"],
                    help="parallelism layout across chips: pure data, "
                         "pure ZeRO-3 fsdp, data×2-way-fsdp (train), or "
                         "tensor (serve: --decode/--traffic shard the "
                         "engine over `tensor`=--chips; A/B degree 1 "
                         "vs 4 vs 8)")
    ap.add_argument("--preset", default="",
                    help="model preset override (e.g. gpt2-medium for the "
                         "fsdp benchmark); default gpt2 on TPU, a toy "
                         "preset when the caller pinned the CPU")
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch override (default 24/chip on TPU)")
    ap.add_argument("--steps", type=int, default=0,
                    help="timed steps override")
    ap.add_argument("--remat", default="",
                    choices=["", "full", "mlp_only", "dots_nb"],
                    help="remat policy override; default mlp_only at "
                         "the default batch (the measured-best b24 "
                         "config), full remat otherwise")
    ap.add_argument("--ce-impl", default="",
                    choices=["", "dense", "streaming_xla", "pallas"],
                    help="cross-entropy implementation: dense logits, "
                         "XLA-scan vocab tiles, or the fused pallas "
                         "lm-head+CE kernel (default: config default)")
    ap.add_argument("--flash-resident", default="",
                    choices=["", "auto", "on", "off"],
                    help="resident-kv flash attention selection for this "
                         "run (RAYTPU_FLASH_RESIDENT env var still "
                         "overrides; default: config default)")
    ap.add_argument("--decode", action="store_true",
                    help="benchmark the serve path instead of training: "
                         "one batched prefill dispatch (TTFT) + jitted "
                         "greedy decode steps (tokens/s); emits "
                         "gpt2_decode_prefill_ttft_ms and "
                         "gpt2_decode_tokens_per_sec JSON lines")
    ap.add_argument("--prompt-len", type=int, default=0,
                    help="--decode prompt length (default 128 on TPU)")
    ap.add_argument("--new-tokens", type=int, default=0,
                    help="--decode generated tokens (default 64 on TPU)")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="--decode with speculative decoding: draft k "
                         "tokens per round, one batched verify "
                         "dispatch (0 = off); emits "
                         "gpt2_decode_spec_tokens_per_sec and "
                         "spec accept-rate JSON lines")
    ap.add_argument("--spec-draft", default="aligned",
                    help="--spec-k draft: 'aligned' (a draft with the "
                         "TARGET's family/preset/seed — acceptance "
                         "~1.0, isolates the dispatch-amortization "
                         "ceiling), 'ngram', or '<family>:<preset>'")
    ap.add_argument("--train", action="store_true",
                    help="benchmark through the trainwatch loop "
                         "(train/goodput.py) instead of the raw AOT "
                         "harness: build_train_step(health=True) driven "
                         "by a data-wait-probed batch iterator; emits "
                         "train_goodput and train_data_wait_ms_p50/p99 "
                         "JSON lines with the full step anatomy in "
                         "detail")
    ap.add_argument("--traffic", action="store_true",
                    help="benchmark the continuous serve engine under "
                         "synthetic shared-prefix Poisson traffic "
                         "(serve/traffic.py); emits prefix-hit-rate and "
                         "SLO-attainment JSON lines")
    ap.add_argument("--requests", type=int, default=0,
                    help="--traffic request count (default 64 on TPU)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="--traffic fleet size: N>1 drives a multi-"
                         "tenant mixture through N continuous-engine "
                         "replicas behind the prefix-affinity router "
                         "(serve/router.py build_llm_fleet); emits "
                         "router_prefix_hit_rate and per-tenant "
                         "slo_attainment lines")
    ap.add_argument("--kv-layout", default="paged",
                    choices=["dense", "paged"],
                    help="--traffic KV-cache layout (paged enables "
                         "prefix reuse; dense is the parity oracle)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="--traffic chunked streaming prefill A/B: "
                         "switch to the two-tenant long-prompt mixture "
                         "and admit long prompts as N-token "
                         "block-aligned chunks interleaved with decode "
                         "waves (0 = same mixture, one-shot prefill — "
                         "the A/B control; paged layout only); emits "
                         "per-tenant ttft_ms_p99 lines")
    ap.add_argument("--prefill-replicas", type=int, default=None,
                    help="--traffic disaggregated serving A/B: build "
                         "this many role='prefill' replicas alongside "
                         "--decode-replicas role='decode' replicas "
                         "(serve/router.py build_llm_fleet) with "
                         "block-granular KV handoff between them; "
                         "both flags required together; emits "
                         "handoff_ms_p99 and per-role pool-occupancy "
                         "lines")
    ap.add_argument("--decode-replicas", type=int, default=None,
                    help="--traffic disaggregated serving: decode-"
                         "role replica count (see --prefill-replicas)")
    ap.add_argument("--handoff-staged", action="store_true",
                    help="--traffic disaggregated serving: force the "
                         "D2H→H2D host-staging handoff hop (the "
                         "cross-process path) instead of the same-"
                         "process device fast path")
    ap.add_argument("--chaos-freeze-replica", type=int, default=None,
                    help="--traffic --replicas N chaos A/B: freeze "
                         "this replica's engine loop (by build-order "
                         "index) mid-traffic via seeded fault "
                         "injection (serve/chaos.py); healthwatch "
                         "detects the death and the router routes "
                         "around it; emits time_to_detect_ms and "
                         "requests_requeued_on_death lines")
    ap.add_argument("--kv-host-tier-bytes", type=int, default=None,
                    help="--traffic tiered host-RAM KV cache A/B: give "
                         "the engine's BlockPager a host tier of this "
                         "byte budget so LRU-evicted prefix blocks "
                         "re-admit via H2D copy instead of re-prefill "
                         "(serve/kv_tier.py; paged layout only; omit "
                         "for the tier-off control); emits "
                         "kv_tier_hit_rate lines")
    ap.add_argument("--profile", default="",
                    help="capture an XLA device trace of the timed "
                         "region into this directory "
                         "(util/state.py profile_device; view with "
                         "tensorboard/xprof)")
    ap.add_argument("--no-ledger", action="store_true",
                    help="do not append this run's metric lines to "
                         "BENCH_HISTORY.jsonl "
                         "(ray_tpu/tools/perfledger)")
    ap.add_argument("--autopilot", action="store_true",
                    help="append a roofline-attribution JSON line "
                         "(ray_tpu/tools/autopilot attribute over the "
                         "programs this run registered) after the "
                         "metric lines")
    return ap.parse_args(argv)


#: metric records emitted by this run (mirrored into the perf ledger
#: unless --no-ledger)
_EMITTED = []


def emit(record) -> None:
    print(json.dumps(record))
    _EMITTED.append(record)


def _maybe_autopilot(args) -> None:
    """`--autopilot`: one extra JSON line attributing the programs this
    run registered (compute-bound vs HBM-bound vs the device ridge,
    ranked by headroom-weighted time share).  Emitted through emit() so
    it rides into the ledger with the metric lines.  Best-effort."""
    if not getattr(args, "autopilot", False):
        return
    try:
        from ray_tpu.tools.autopilot import attribute_registry

        emit({"autopilot": attribute_registry()})
    except Exception as e:  # noqa: BLE001 - attribution is best-effort
        sys.stderr.write(f"bench: autopilot attribution failed: "
                         f"{e!r}\n")


def _ledger_append(args) -> None:
    """Persist this run's JSON lines into BENCH_HISTORY.jsonl so the
    bench trajectory survives the terminal (perfledger check/report
    read it back).  Best-effort: a ledger failure never breaks the
    bench contract of always printing its lines."""
    _maybe_autopilot(args)
    if getattr(args, "no_ledger", False) or not _EMITTED:
        return
    try:
        from ray_tpu.tools import perfledger

        n = perfledger.append_records(_EMITTED, source="bench")
        sys.stderr.write(f"bench: {n} record(s) appended to "
                         f"{perfledger.history_path()}\n")
    except Exception as e:  # noqa: BLE001 - ledger is best-effort
        sys.stderr.write(f"bench: perf ledger append failed: {e!r}\n")


def _maybe_profile(logdir: str):
    """`--profile <dir>` context: a device trace of the timed region
    (no-op without the flag)."""
    import contextlib

    if not logdir:
        return contextlib.nullcontext()
    from ray_tpu.util.state import profile_device

    return profile_device(logdir)

def require_backend():
    """The first device, after checking it is what the caller asked
    for.  bench.py measures the chip: it runs on a TPU, or — when the
    caller itself set ``JAX_PLATFORMS=cpu`` — as a toy-sized CPU smoke of
    the control flow under ``*_cpu_smoke`` metric names.  Anything else
    (no chip answering, JAX quietly on its CPU backend) is an error; no
    probing, retrying or re-running on another platform."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and \
            os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            f"bench: no TPU (JAX found platform {dev.platform!r}); "
            f"bench.py measures the chip.  Set JAX_PLATFORMS=cpu "
            f"yourself for a toy-sized smoke of the control flow.")
    return dev


def peak_flops_per_chip() -> float:
    """Dense bf16 peak FLOPs/s per chip — single source of truth is
    the perf observatory's table."""
    from ray_tpu._private.device_stats import \
        peak_flops_per_chip as _peak

    return _peak()


def time_config(batch, seq=1024, n_steps=20, preset="gpt2", mesh="data",
                n_devices=0, **overrides):
    """Compile and time `n_steps` donated train steps of the GPT-2
    flagship under a mesh spanning every local chip (`mesh` selects the
    data / fsdp / data×fsdp layout; `n_devices` restricts the mesh to
    the first N devices, 0 = all).

    Returns (tok_s_per_chip, mfu, final_loss, n_chips, cost): `cost`
    carries the COMPILER's own numbers for the step — AOT
    ``lower().compile()`` cost_analysis FLOPs (per chip and global,
    assuming XLA's even SPMD split), memory_analysis peak HBM, compile
    walltime, the hand-counted ``model_flops`` (6·N·tokens), and
    ``mfu_xla`` (roofline MFU from XLA FLOPs rather than the 6·N·D
    formula) — empty when AOT compilation is unavailable.  Shared by
    main() and sweep_tpu.py so the timing methodology (donation, mesh,
    host-transfer fence, per-chip normalization) has one source of
    truth."""
    import jax
    import optax

    from ray_tpu.models import (gpt2_config, gpt2_init, gpt2_logical_axes,
                                gpt2_loss)
    from ray_tpu.models.gpt2 import gpt2_param_count
    from ray_tpu.parallel import MeshSpec, make_mesh
    from ray_tpu.parallel.sharding import param_shardings, shard_params

    devices = list(jax.devices())
    if n_devices:
        devices = devices[:n_devices]
    n_chips = len(devices)
    cfg = gpt2_config(preset, max_seq=seq, **overrides)
    spec = {
        "data": MeshSpec(data=-1),
        "fsdp": MeshSpec(fsdp=-1),
        "data_fsdp": MeshSpec(data=-1,
                              fsdp=2 if n_chips % 2 == 0 else 1),
    }[mesh]
    mesh = make_mesh(spec, devices=devices)
    axes = gpt2_logical_axes(cfg)
    tx = optax.adamw(3e-4, weight_decay=0.1)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)

    with jax.set_mesh(mesh):
        params = shard_params(params, axes, mesh)
        opt_state = tx.init(params)
        p_shard = param_shardings(axes, mesh)

        @functools.partial(jax.jit, in_shardings=(p_shard, None, None),
                           donate_argnums=(0, 1))
        def train_step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(
                lambda p: gpt2_loss(p, batch, cfg))(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, seq + 1), 0, cfg.vocab_size)
        data = {"tokens": tokens}

        # AOT compile: lower().compile() once, so the SAME executable
        # both runs the timed loop and yields the compiler's
        # cost_analysis/memory_analysis — no double compile, and the
        # observatory registry records the event.
        from ray_tpu._private.device_stats import (_cost_summary,
                                                   get_registry)

        t_c0 = time.perf_counter()
        step = train_step.lower(params, opt_state, data).compile()
        cost = _cost_summary(step)
        compile_s = time.perf_counter() - t_c0
        get_registry().record_compile("bench.train_step", compile_s,
                                      cost=cost or None)
        # warmup + steady-state timing, fenced by a host transfer of
        # the loss: the final loss depends on every prior step's
        # params, so fetching it waits for the whole chain.
        params, opt_state, loss = step(params, opt_state, data)
        float(loss)
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, loss = step(params, opt_state, data)
        final_loss = float(loss)
        dt = time.perf_counter() - t0
        # book the steady-state window into the observatory: the loop
        # above dispatches async and only the float(loss) fence is a
        # real sync, so per-step walltime is dt/n_steps, not the
        # un-fenced dispatch intervals.  Without this bench.train_step
        # records compiles but zero invokes, and the autopilot has no
        # time_share to attribute on train sweeps.
        reg = get_registry()
        for _ in range(n_steps):
            reg.record_invoke("bench.train_step", dt / max(1, n_steps))

    n_params = gpt2_param_count(cfg)
    tok_s_chip = batch * seq * n_steps / dt / max(1, n_chips)
    peak = peak_flops_per_chip()
    mfu = 6 * n_params * tok_s_chip / peak
    # compiler-vs-hand-count cross-check (satellite: stale 6·N·D
    # formulas after model refactors should be visible).  XLA reports
    # per-partition FLOPs for SPMD programs; the even-split assumption
    # is exact for the pure-data layouts this harness uses.
    cost["model_flops"] = float(6 * n_params * batch * seq)
    cost["compile_seconds"] = round(compile_s, 3)
    if cost.get("xla_flops"):
        cost["xla_flops_per_chip"] = cost["xla_flops"]
        cost["xla_flops"] = cost["xla_flops"] * max(1, n_chips)
        cost["mfu_xla"] = (cost["xla_flops"] * n_steps / dt
                           / (max(1, n_chips) * peak))
    return tok_s_chip, mfu, final_loss, n_chips, cost


def decode_mesh(tensor_degree):
    """(mesh, n_chips) for a tensor-parallel serve bench — None/1 when
    the degree is 1 (single-chip path unchanged).  Uses the first
    `tensor_degree` local devices and fails when fewer are attached."""
    if tensor_degree <= 1:
        return None, 1
    import jax

    from ray_tpu.parallel import MeshSpec, make_mesh

    devices = list(jax.devices())[:tensor_degree]
    if len(devices) < tensor_degree:
        raise ValueError(f"tensor degree {tensor_degree} needs "
                         f"{tensor_degree} devices, have {len(devices)}")
    return (make_mesh(MeshSpec(tensor=tensor_degree), devices=devices),
            tensor_degree)


def time_decode(batch, prompt_len=128, new_tokens=64, preset="gpt2",
                mesh=None, **overrides):
    """Compile and time the GPT-2 serve path: ONE batched prefill
    dispatch of a (batch, prompt_len) prompt (TTFT, 3 repetitions)
    followed by `new_tokens` jitted greedy decode steps against the KV
    cache (steady-state decode tokens/s).

    Returns (ttft_best_ms, tok_s, engine_stats, n_chips) — the
    measurements flow through the serve engine-telemetry layer
    (serve/telemetry.py), so the reported p50/p95/p99 TTFT and
    inter-token percentiles come from the SAME code path
    `engine_stats()` serves in production.  Per-step timestamps are
    host-side dispatch intervals (no extra device syncs; under async
    dispatch they track device step time once the pipeline
    backpressures).  `mesh` tensor-parallelises the whole path: params
    are committed under DECODE_RULES and the prefilled cache inherits
    their sharding through GSPMD, so the step program spans every mesh
    chip.  Shared by main(--decode) and sweep_tpu.py decode variants
    so the methodology has one source of truth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import (gpt2_config, gpt2_init,
                                gpt2_logical_axes)
    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.gpt2_decode import decode_step, prefill
    from ray_tpu.serve.telemetry import EngineTelemetry

    cfg = gpt2_config(preset, **overrides)
    if prompt_len + new_tokens > cfg.max_seq:
        raise ValueError(f"prompt_len {prompt_len} + new_tokens "
                         f"{new_tokens} exceeds max_seq={cfg.max_seq}")
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    n_chips = 1
    if mesh is not None:
        from ray_tpu.parallel.sharding import (DECODE_RULES,
                                               shard_by_shape)

        params = shard_by_shape(params, gpt2_logical_axes(cfg), mesh,
                                DECODE_RULES)
        n_chips = int(mesh.size)
    toks = jax.random.randint(jax.random.PRNGKey(1),
                              (batch, prompt_len), 0, cfg.vocab_size)
    tail = make_vocab_tail_mask(cfg)
    telemetry = EngineTelemetry("bench_decode", max_slots=batch)

    @jax.jit
    def run_prefill(p, t):
        logits, cache = prefill(p, t, cfg)
        return sample_token(logits, None, 0.0, tail), cache

    @jax.jit
    def run_step(p, cache, t):
        logits, cache = decode_step(p, cache, t, cfg)
        return sample_token(logits, None, 0.0, tail), cache

    # an active mesh is what lets the flash kernel of a >=1024-token
    # prefill run per shard (ops/attention.py causal_attention)
    import contextlib

    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        # warmup / compile both programs
        tok, cache = run_prefill(params, toks)
        tok2, _ = run_step(params, cache, tok)
        jax.block_until_ready(tok2)

        ttfts = []
        for rep in range(3):
            rec = telemetry.record_enqueue(prompt_len)
            t0 = time.perf_counter()
            telemetry.record_admit(rec, slot=0, bucket=prompt_len, now=t0)
            tok, cache = run_prefill(params, toks)
            jax.block_until_ready(tok)
            telemetry.record_first_token(rec)
            ttfts.append(time.perf_counter() - t0)
            if rep < 2:  # only the last rep's request runs the decode loop
                telemetry.record_finish(rec, n_tokens=1)
        ttft_ms = min(ttfts) * 1000.0

        t0 = time.perf_counter()
        prev = t0
        for _ in range(new_tokens):
            tok, cache = run_step(params, cache, tok)
            now = time.perf_counter()
            telemetry.record_step(batch, now - prev, now=now)
            prev = now
        jax.block_until_ready(tok)
        dt = time.perf_counter() - t0
        tok_s = batch * new_tokens / dt
        telemetry.record_finish(rec, n_tokens=new_tokens)
    return ttft_ms, tok_s, telemetry.engine_stats(), n_chips


def main_decode(args, on_tpu: bool) -> None:
    """--decode: inference metrics in the same machine-readable shape
    as the train metric — one JSON line per metric, each carrying the
    other value in detail.  No published decode baseline exists, so
    vs_baseline is null."""
    import jax

    if on_tpu:
        batch = args.batch or 8
        preset = args.preset or "gpt2"
        prompt_len = args.prompt_len or 128
        new_tokens = args.new_tokens or 64
        base = "gpt2_decode"
    else:  # caller pinned the CPU: toy-sized smoke of the control flow
        batch = args.batch or 4
        preset = args.preset or "tiny"
        prompt_len = args.prompt_len or 16
        new_tokens = args.new_tokens or 8
        base = "gpt2_decode_cpu_smoke"
    cfg_kw = {}
    if args.flash_resident:
        cfg_kw["flash_resident"] = args.flash_resident
    mesh, n_chips = (decode_mesh(args.chips or 1)
                     if args.mesh == "tensor" else (None, 1))
    if mesh is not None:
        base += "_sharded"
    with _maybe_profile(args.profile):
        ttft_best_ms, tok_s, stats, n_chips = time_decode(
            batch, prompt_len=prompt_len, new_tokens=new_tokens,
            preset=preset, mesh=mesh, **cfg_kw)
    # Headline TTFT is the p50 from engine_stats() (the same snapshot
    # the serve layer exposes), not the ad-hoc best-of-3 min — that
    # stays in detail as ttft_best_ms for continuity with old lines.
    ttft_ms = stats["ttft_ms"]["p50"]
    if ttft_ms is None:  # defensive: stats recorded nothing
        ttft_ms = ttft_best_ms
    engine = {"ttft_ms": stats["ttft_ms"],
              "inter_token_ms": stats["inter_token_ms"],
              "tokens_per_sec": stats["tokens_per_sec"]}
    detail = {"chips": n_chips, "batch": batch,
              "prompt_len": prompt_len,
              "new_tokens": new_tokens, "preset": preset,
              "mesh": ({"tensor": n_chips} if mesh is not None else {}),
              "flash_resident": args.flash_resident or "auto",
              "backend": jax.default_backend(),
              "ttft_best_ms": round(ttft_best_ms, 2), "engine": engine}
    emit({
        "metric": f"{base}_prefill_ttft_ms",
        "value": round(ttft_ms, 2), "unit": "ms", "vs_baseline": None,
        "detail": dict(detail, tokens_per_sec=round(tok_s, 1))})
    emit({
        "metric": f"{base}_tokens_per_sec",
        "value": round(tok_s, 1), "unit": "tokens/s",
        "vs_baseline": None,
        "detail": dict(detail, prefill_ttft_ms=round(ttft_ms, 2))})
    # Per-chip normalization is the A/B-able number for tensor degree
    # 1 vs 4 vs 8: raw tokens/s conflates chip count with efficiency.
    emit({
        "metric": f"{base}_tokens_per_sec_per_chip",
        "value": round(tok_s / max(1, n_chips), 1),
        "unit": "tokens/s/chip", "vs_baseline": None,
        "detail": dict(detail, tokens_per_sec=round(tok_s, 1),
                       prefill_ttft_ms=round(ttft_ms, 2))})


def time_decode_spec(batch, prompt_len=128, new_tokens=64,
                     preset="gpt2", spec_k=4, spec_draft="aligned",
                     kv_layout="dense", mesh=None, seed=0,
                     config_overrides=None):
    """Time the CONTINUOUS engine with speculative decoding: `batch`
    concurrent requests through build_llm_deployment(spec_decode=...),
    greedy, measured end-to-end through the same engine-telemetry
    layer production serves.

    'aligned' draft = a draft model with the target's own
    family/preset/seed — its proposals always match the target argmax,
    so acceptance is ~1.0 and the run measures the pure
    dispatch-amortization ceiling (the floor on target dispatches per
    token at a given k).  Real drafts land between this and the
    non-spec engine.

    Returns (tok_s, stats, dispatches_per_token, n_chips):
    dispatches_per_token counts TARGET model dispatches per emitted
    token, slot-normalized — one prefill per request plus one verify
    per slot-round, over all emitted tokens.  Non-spec decode is
    exactly 1.0 by construction; spec at acceptance rate a gives
    ~1/(1 + a*k)."""
    import asyncio

    import numpy as np

    from ray_tpu.serve.llm import SpecConfig, build_llm_deployment

    draft = (f"gpt2:{preset}" if spec_draft == "aligned"
             else spec_draft)
    dep = build_llm_deployment(
        "gpt2", preset, scheduler="continuous",
        max_new_tokens=new_tokens, max_slots=batch,
        prefill_bucket=max(16, prompt_len), kv_layout=kv_layout,
        mesh=mesh, seed=seed,
        spec_decode=SpecConfig(draft=draft, k=spec_k),
        config_overrides=config_overrides)
    inst = dep.func_or_class()
    rng = np.random.default_rng(1)
    vocab = int(inst.cfg.vocab_size)
    prompts = [rng.integers(0, vocab, size=prompt_len).astype(np.int32)
               for _ in range(batch)]

    async def go():
        try:
            return await asyncio.gather(*[inst(p) for p in prompts])
        finally:
            inst.shutdown_engine()

    t0 = time.perf_counter()
    outs = asyncio.run(go())
    dt = time.perf_counter() - t0
    stats = inst.engine_stats()
    n_tokens = sum(len(o) - prompt_len for o in outs)
    spec = stats["spec"]
    # one target prefill per request + one verify per slot-round
    dispatches = batch + spec["rounds"]
    n_chips = int(mesh.size) if mesh is not None else 1
    return (n_tokens / dt, stats, dispatches / max(1, n_tokens),
            n_chips)


def main_decode_spec(args, on_tpu: bool) -> None:
    """--decode --spec-k K: speculative decoding on the continuous
    engine, same machine-readable shape as the plain decode metrics.
    Headlines are decode_spec tokens/s and the measured acceptance
    rate; target dispatches per token (the amortization the whole
    feature buys) rides in detail.  No published baseline exists, so
    vs_baseline is null."""
    import jax

    if on_tpu:
        batch = args.batch or 8
        preset = args.preset or "gpt2"
        prompt_len = args.prompt_len or 128
        new_tokens = args.new_tokens or 64
        base = "gpt2_decode"
        overrides = None
    else:  # caller pinned the CPU: toy-sized smoke of the control flow
        import jax.numpy as jnp

        batch = args.batch or 4
        preset = args.preset or "nano"
        prompt_len = args.prompt_len or 16
        new_tokens = args.new_tokens or 12
        base = "gpt2_decode_cpu_smoke"
        overrides = {"dtype": jnp.float32, "use_flash": False,
                     "remat": False}
    mesh, n_chips = (decode_mesh(args.chips or 1)
                     if args.mesh == "tensor" else (None, 1))
    spec_base = base.replace("_decode", "_decode_spec")
    if mesh is not None:
        spec_base += "_sharded"
    with _maybe_profile(args.profile):
        tok_s, stats, dpt, n_chips = time_decode_spec(
            batch, prompt_len=prompt_len, new_tokens=new_tokens,
            preset=preset, spec_k=args.spec_k,
            spec_draft=args.spec_draft, kv_layout=args.kv_layout,
            mesh=mesh, config_overrides=overrides)
    spec = stats["spec"]
    detail = {"chips": n_chips, "batch": batch,
              "prompt_len": prompt_len, "new_tokens": new_tokens,
              "preset": preset, "spec_k": args.spec_k,
              "spec_draft": args.spec_draft,
              "kv_layout": args.kv_layout,
              "mesh": ({"tensor": n_chips} if mesh is not None
                       else {}),
              "backend": jax.default_backend(),
              "target_dispatches_per_token": round(dpt, 4),
              "spec": spec}
    emit({
        "metric": f"{spec_base}_tokens_per_sec",
        "value": round(tok_s, 1), "unit": "tokens/s",
        "vs_baseline": None,
        "detail": dict(detail,
                       accept_rate=spec["accept_rate"])})
    emit({
        "metric": f"{spec_base}_accept_rate",
        "value": spec["accept_rate"], "unit": "ratio",
        "vs_baseline": None,
        "detail": dict(detail, tokens_per_sec=round(tok_s, 1))})


def main_traffic(args, on_tpu: bool) -> None:
    """--traffic: the continuous engine under seeded shared-prefix
    Poisson load (serve/traffic.py run_traffic — the same entry the
    tier-1 traffic test and sweep_tpu.py traffic variants call).
    Headline metrics are the paged KV cache's prefix-hit rate and the
    fraction of requests finishing inside the latency SLO; throughput
    and shed counts ride in detail.  Per-objective engine-side SLO
    attainment (SLOConfig: TTFT at half the e2e bound) emits its own
    `{base}_{objective}_slo_attainment` lines; `--spec-k K` runs the
    traffic through the speculative engine and adds accept-rate
    lines.  No published baseline exists, so vs_baseline is null.
    `--replicas N` (N>1) switches to the fleet path below, as does
    the disaggregated `--prefill-replicas/--decode-replicas` pair."""
    if args.replicas > 1 or args.prefill_replicas \
            or args.decode_replicas:
        return main_traffic_fleet(args, on_tpu)
    import jax

    from ray_tpu.serve.batching import AdmissionPolicy
    from ray_tpu.serve.llm import SpecConfig
    from ray_tpu.serve.slo import SLOConfig
    from ray_tpu.serve.traffic import TrafficSpec, run_traffic

    if on_tpu:
        base, preset = "gpt2_traffic", "gpt2"
        n = args.requests or 64
        spec = TrafficSpec(num_requests=n, seed=0, rate_rps=32.0,
                           num_prefix_groups=4, prefix_len=256,
                           p_shared=0.75, tail_len_mean=32.0,
                           tail_len_max=128, vocab=50000)
        kw = dict(max_slots=8, max_new_tokens=64, prefill_bucket=128,
                  latency_slo_ms=20000.0, time_scale=1.0)
    else:  # caller pinned the CPU: toy-sized smoke of the control flow
        base, preset = "gpt2_traffic_cpu_smoke", "nano"
        import jax.numpy as jnp

        n = args.requests or 16
        spec = TrafficSpec(num_requests=n, seed=0, rate_rps=100.0,
                           num_prefix_groups=2, prefix_len=32,
                           p_shared=0.75, tail_len_mean=6.0,
                           tail_len_max=16, vocab=500)
        kw = dict(max_slots=4, max_new_tokens=8, prefill_bucket=16,
                  latency_slo_ms=60000.0, time_scale=0.0,
                  config_overrides={"dtype": jnp.float32,
                                    "use_flash": False})
    if args.prefill_chunk is not None:
        import dataclasses

        from ray_tpu.serve.traffic import TenantSpec

        # the chunked-prefill A/B workload: an interactive tenant with
        # the spec's short Poisson tails plus a batch tenant flooding
        # with fixed long prompts (prompt fits max_seq: prefix + long
        # tail + max_new).  --prefill-chunk 0 runs the SAME mixture
        # one-shot, so the two runs A/B on identical traffic.
        base += "_long"
        spec = dataclasses.replace(spec, tenants=(
            TenantSpec("interactive", rate_share=3.0,
                       slo_class="interactive"),
            TenantSpec("batch", rate_share=1.0, slo_class="batch",
                       prompt_len=640 if on_tpu else 80),
        ))
        kw["prefill_chunk_tokens"] = args.prefill_chunk or None
    if args.kv_host_tier_bytes:
        base += "_tier"
        kw["kv_host_tier_bytes"] = args.kv_host_tier_bytes
    mesh, n_chips = (decode_mesh(args.chips or 1)
                     if args.mesh == "tensor" else (None, 1))
    if mesh is not None:
        base += "_sharded"
    # engine-side SLO targets derived from the client latency bound:
    # TTFT gets half the e2e budget (prefill must not eat the window)
    slo_cfg = SLOConfig(ttft_ms=kw["latency_slo_ms"] / 2,
                        e2e_ms=kw["latency_slo_ms"])
    spec_cfg = None
    if args.spec_k > 0:
        base += "_spec"
        draft = (f"gpt2:{preset}" if args.spec_draft == "aligned"
                 else args.spec_draft)
        spec_cfg = SpecConfig(draft=draft, k=args.spec_k)
    rep = run_traffic(
        spec, family="gpt2", preset=preset,
        kv_layout=args.kv_layout, mesh=mesh,
        admission_policy=AdmissionPolicy(max_queue_depth=4 * n),
        slo=slo_cfg, spec_decode=spec_cfg,
        **kw)
    eng = rep["engine"]
    # Per-chip normalized throughput + the mesh axes the engine
    # actually ran with (from its own stats block — axes of size 1 are
    # already dropped there), so sharded traffic lines are A/B-able
    # against the single-chip ones without re-deriving chip counts.
    mesh_axes = eng.get("mesh", {}).get("axes", {})
    tok_s = eng["tokens_per_sec"]
    detail = {"chips": n_chips, "requests": rep["offered"],
              "completed": rep["completed"], "shed": rep["shed"],
              "kv_layout": args.kv_layout, "preset": preset,
              "mesh_axes": mesh_axes,
              "backend": jax.default_backend(),
              "latency_ms": rep["latency_ms"],
              "tokens_per_sec": tok_s,
              "tokens_per_sec_per_chip":
                  (round(tok_s / max(1, n_chips), 1)
                   if isinstance(tok_s, (int, float)) else tok_s),
              "ttft_ms": eng["ttft_ms"],
              "kv_cache": eng.get("kv_cache"),
              "rejections_by_reason": eng["rejections_by_reason"]}
    if args.prefill_chunk is not None:
        detail["prefill_chunk_tokens"] = args.prefill_chunk or None
        detail["prefill_chunks"] = rep.get("prefill_chunks")
    if args.kv_host_tier_bytes:
        detail["kv_host_tier_bytes"] = args.kv_host_tier_bytes
        detail["kv_tier"] = eng.get("kv_tier")
    if spec_cfg is not None:
        # spec counters join every traffic record so ledger series
        # cover spec+traffic runs, not just --decode --spec-k
        eng_spec = eng.get("spec") or {}
        detail["spec"] = {"k": args.spec_k,
                          "draft": spec_cfg.draft,
                          "accept_rate": eng_spec.get("accept_rate"),
                          "rounds": eng_spec.get("rounds"),
                          "proposed": eng_spec.get("proposed"),
                          "accepted": eng_spec.get("accepted")}
    emit({
        "metric": f"{base}_prefix_hit_rate",
        "value": rep["prefix_hit_rate"], "unit": "fraction",
        "vs_baseline": None,
        "detail": dict(detail,
                       slo_attainment=rep["slo_attainment"])})
    emit({
        "metric": f"{base}_slo_attainment",
        "value": rep["slo_attainment"], "unit": "fraction",
        "vs_baseline": None,
        "detail": dict(detail,
                       latency_slo_ms=rep["latency_slo_ms"],
                       prefix_hit_rate=rep["prefix_hit_rate"])})
    # per-objective engine-side attainment (serve/slo.py burn-rate
    # tracker): one line per configured objective
    for name, obj in (rep.get("slo") or {}).items():
        if not isinstance(obj.get("attainment"), (int, float)):
            continue
        emit({
            "metric": f"{base}_{name}_slo_attainment",
            "value": obj["attainment"], "unit": "fraction",
            "vs_baseline": None,
            "detail": dict(detail, target_ms=obj["target_ms"],
                           burn_rate=obj["burn_rate"])})
    if spec_cfg is not None and isinstance(
            rep.get("spec_accept_rate"), (int, float)):
        # base already carries the "_spec" suffix in spec mode, so
        # this lands as `{...}_spec_accept_rate`
        emit({
            "metric": f"{base}_accept_rate",
            "value": rep["spec_accept_rate"], "unit": "ratio",
            "vs_baseline": None,
            "detail": dict(detail, rounds=rep.get("spec_rounds"))})
    # per-tenant TTFT p99 — the chunked-prefill headline: interactive
    # TTFT under the long-prompt flood, A/B-able across chunk sizes
    for tname in ("interactive", "batch"):
        v = rep.get(f"{tname}_ttft_ms_p99")
        if isinstance(v, (int, float)):
            emit({
                "metric": f"{base}_{tname}_ttft_ms_p99",
                "value": v, "unit": "ms", "vs_baseline": None,
                "detail": detail})
    _emit_anatomy(base, rep, detail)
    _emit_kvscope(base, rep, detail)


def _emit_kvscope(base: str, rep: dict, detail: dict) -> None:
    """kvscope headlines shared by --traffic solo and --replicas N:
    KV pool pressure (p95 occupancy over the run's engine waves) and
    cache-thrash waste (fraction of prefilled tokens that re-filled
    previously-resident prefixes).  Both lower-is-better in the
    ledger; the host-tier hit rate (fraction of second-chance probes
    the tier absorbed) is higher-is-better and reads 0.0 when no tier
    was configured, so tier-on/off runs stay A/B-able."""
    for field, unit in (("kv_occupancy_p95", "fraction"),
                        ("reprefill_waste_frac", "fraction"),
                        ("kv_tier_hit_rate", "fraction")):
        v = rep.get(field)
        if isinstance(v, (int, float)):
            emit({
                "metric": f"{base}_{field}",
                "value": v, "unit": unit, "vs_baseline": None,
                "detail": detail})


def _emit_anatomy(base: str, rep: dict, detail: dict) -> None:
    """Tracebus per-token anatomy lines shared by --traffic solo and
    --replicas N: inter-token latency percentiles plus the p99
    TTFT-side critical-path total (its decomposition — router wait /
    queue wait / requeue / prefill — rides in detail)."""
    for q in ("p50", "p99"):
        v = rep.get(f"itl_ms_{q}")
        if isinstance(v, (int, float)):
            emit({
                "metric": f"{base}_itl_ms_{q}",
                "value": v, "unit": "ms", "vs_baseline": None,
                "detail": dict(detail,
                               tpot_ms=(rep.get("latency_anatomy")
                                        or {}).get("tpot_ms"))})
    cp = rep.get("ttft_critical_path") or {}
    if isinstance(cp.get("total_p99_ms"), (int, float)):
        emit({
            "metric": f"{base}_ttft_critical_path",
            "value": cp["total_p99_ms"], "unit": "ms",
            "vs_baseline": None,
            "detail": dict(detail, critical_path=cp)})


def main_traffic_fleet(args, on_tpu: bool) -> None:
    """--traffic --replicas N: a two-tenant mixture (interactive +
    batch, disjoint prefix pools) through N continuous-engine replicas
    behind the prefix-affinity router with WFQ tenant classes
    (serve/router.py build_llm_fleet / serve/traffic.py
    run_traffic_fleet — the same entry `sweep_tpu.py`'s traffic_fleet
    mode calls).  Headline metrics: the FLEET prefix-hit rate (pooled
    over replicas — routing quality, not just cache quality) and
    per-tenant `{tenant}_{objective}_slo_attainment`."""
    import jax

    from ray_tpu.serve.slo import SLOConfig
    from ray_tpu.serve.traffic import (TenantSpec, TrafficSpec,
                                       run_traffic_fleet)

    if on_tpu:
        base, preset = "gpt2_traffic_fleet", "gpt2"
        n = args.requests or 64
        slo_ms = 20000.0
        tenants = (
            TenantSpec("interactive", rate_share=1.0,
                       slo_class="interactive", prefix_groups=(0, 1),
                       ttft_slo_ms=slo_ms / 2, e2e_slo_ms=slo_ms),
            TenantSpec("batch", rate_share=1.0, slo_class="batch",
                       prefix_groups=(2, 3), e2e_slo_ms=2 * slo_ms))
        spec = TrafficSpec(num_requests=n, seed=0, rate_rps=32.0,
                           num_prefix_groups=4, prefix_len=256,
                           p_shared=0.75, tail_len_mean=32.0,
                           tail_len_max=128, vocab=50000,
                           tenants=tenants)
        kw = dict(max_slots=8, max_new_tokens=64, prefill_bucket=128,
                  time_scale=1.0)
    else:  # caller pinned the CPU: toy-sized smoke of the control flow
        base, preset = "gpt2_traffic_fleet_cpu_smoke", "nano"
        import jax.numpy as jnp

        n = args.requests or 16
        slo_ms = 60000.0
        tenants = (
            TenantSpec("interactive", rate_share=1.0,
                       slo_class="interactive", prefix_groups=(0,),
                       ttft_slo_ms=slo_ms / 2, e2e_slo_ms=slo_ms),
            TenantSpec("batch", rate_share=1.0, slo_class="batch",
                       prefix_groups=(1,), e2e_slo_ms=2 * slo_ms))
        spec = TrafficSpec(num_requests=n, seed=0, rate_rps=100.0,
                           num_prefix_groups=2, prefix_len=32,
                           p_shared=0.75, tail_len_mean=6.0,
                           tail_len_max=16, vocab=500,
                           tenants=tenants)
        kw = dict(max_slots=4, max_new_tokens=8, prefill_bucket=16,
                  time_scale=0.0,
                  config_overrides={"dtype": jnp.float32,
                                    "use_flash": False})
    if args.kv_host_tier_bytes:
        base += "_tier"
        kw["kv_host_tier_bytes"] = args.kv_host_tier_bytes
    disagg = bool(args.prefill_replicas or args.decode_replicas)
    if disagg:
        base += "_disagg"
        kw["num_prefill_replicas"] = args.prefill_replicas
        kw["num_decode_replicas"] = args.decode_replicas
        kw["handoff_staged"] = args.handoff_staged
    chaos_freeze = args.chaos_freeze_replica
    if chaos_freeze is not None:
        from ray_tpu.serve.chaos import ChaosConfig
        from ray_tpu.serve.health import HealthConfig

        base += "_chaos"
        # tight thresholds so the CPU-smoke run detects within the
        # freeze window; the freeze outlasts dead_ms by construction
        kw["health"] = HealthConfig(suspect_ms=40.0, dead_ms=120.0,
                                    stall_ms=80.0, probe_ms=5.0)
        kw["chaos"] = ChaosConfig(
            seed=spec.seed, freeze_replica=int(chaos_freeze),
            freeze_after_waves=2, freeze_waves=200,
            freeze_poll_ms=5.0)
    rep = run_traffic_fleet(
        spec, num_replicas=args.replicas, family="gpt2",
        preset=preset, kv_block_size=16,
        slo=SLOConfig(ttft_ms=slo_ms / 2, e2e_ms=slo_ms), **kw)
    fleet = rep["fleet"]
    detail = {"replicas": args.replicas, "requests": rep["offered"],
              "completed": rep["completed"], "shed": rep["shed"],
              "preset": preset, "routing": rep["routing"],
              "wfq": rep["wfq"],
              "backend": jax.default_backend(),
              "latency_ms": rep["latency_ms"],
              "latency_ms_by_tenant": rep["latency_ms_by_tenant"],
              "routed_by_policy":
                  fleet["router"]["routed_by_policy"]}
    if args.kv_host_tier_bytes:
        detail["kv_host_tier_bytes"] = args.kv_host_tier_bytes
        detail["kv_tier"] = fleet.get("kv_tier")
    if disagg:
        detail["num_prefill_replicas"] = args.prefill_replicas
        detail["num_decode_replicas"] = args.decode_replicas
        detail["handoff_staged"] = args.handoff_staged
        detail["handoff"] = rep.get("handoff")
        emit({
            "metric": f"{base}_handoff_ms_p99",
            "value": rep.get("handoff_ms_p99"), "unit": "ms",
            "vs_baseline": None, "detail": detail})
        for key in sorted(rep):
            # {role}_kv_occupancy_{mean,p95} utilization lines
            if key.endswith("_kv_occupancy_p95") \
                    or key.endswith("_kv_occupancy_mean"):
                emit({
                    "metric": f"{base}_{key}",
                    "value": rep[key], "unit": "fraction",
                    "vs_baseline": None, "detail": detail})
    if chaos_freeze is not None:
        detail["chaos_freeze_replica"] = chaos_freeze
        detail["health"] = fleet.get("health")
        emit({
            "metric": f"{base}_time_to_detect_ms",
            "value": rep.get("time_to_detect_ms"), "unit": "ms",
            "vs_baseline": None, "detail": detail})
        emit({
            "metric": f"{base}_requests_requeued_on_death",
            "value": rep.get("requests_requeued_on_death"),
            "unit": "requests", "vs_baseline": None,
            "detail": detail})
    emit({
        "metric": f"{base}_router_prefix_hit_rate",
        "value": rep["router_prefix_hit_rate"], "unit": "fraction",
        "vs_baseline": None, "detail": detail})
    for name, value in sorted(rep["tenant_slo_attainment"].items()):
        if not isinstance(value, (int, float)):
            continue
        emit({
            "metric": f"{base}_{name}",
            "value": value, "unit": "fraction", "vs_baseline": None,
            "detail": dict(detail,
                           tenant_report=rep["tenants"].get(
                               name.split("_", 1)[0]))})
    _emit_anatomy(base, rep, detail)
    _emit_kvscope(base, rep, detail)


def main_train_watch(args, on_tpu: bool) -> None:
    """--train: the trainwatch goodput bench.  Where the default path
    times a raw AOT loop (time_config), this drives the instrumented
    flagship path — ``jax_utils.build_train_step(health=True)`` fed by
    a data-wait-probed batch iterator — and reports what trainwatch
    measured: the rolling goodput ratio (productive device time over
    loop wall, compiles and stalls excluded) and the input-stall
    percentiles, with the full step anatomy in detail.  Health mode
    fences every step, so the device leg is real device time, not
    dispatch time."""
    import numpy as np

    import jax
    import optax

    from ray_tpu.models import (gpt2_config, gpt2_init,
                                gpt2_logical_axes, gpt2_loss)
    from ray_tpu.train import goodput as gp
    from ray_tpu.train.jax_trainer import jax_utils
    from ray_tpu.train.telemetry import train_stats

    preset = args.preset or ("gpt2" if on_tpu else "tiny")
    seq = 1024 if on_tpu else 128
    n_chips = len(jax.devices())
    if args.chips:
        n_chips = min(n_chips, args.chips)
    batch = args.batch or ((8 * n_chips) if on_tpu else 2)
    n_steps = args.steps or (20 if on_tpu else 3)
    overrides = {} if on_tpu else {"use_flash": False}
    cfg = gpt2_config(preset, max_seq=seq, **overrides)

    mesh, axes = None, None
    if n_chips > 1:
        from ray_tpu.parallel import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(data=-1),
                         devices=list(jax.devices())[:n_chips])
        axes = gpt2_logical_axes(cfg)

    tx = optax.adamw(3e-4, weight_decay=0.1)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    import contextlib

    trainer = "bench_train"
    with (jax.set_mesh(mesh) if mesh is not None
          else contextlib.nullcontext()):
        if mesh is not None:
            from ray_tpu.parallel.sharding import shard_params

            params = shard_params(params, axes, mesh)
        opt_state = tx.init(params)
        step = jax_utils.build_train_step(
            lambda p, b: gpt2_loss(p, b, cfg), tx, mesh=mesh,
            logical_axes=axes, health=True, telemetry_name=trainer)

        rng = np.random.RandomState(0)

        def batches():
            while True:
                yield {"tokens": rng.randint(
                    0, cfg.vocab_size,
                    size=(batch, seq + 1)).astype(np.int32)}

        it = gp.watch_data(batches(), trainer=trainer)
        loss = None
        for _ in range(n_steps + 1):   # +1: the first step compiles
            data = next(it)
            params, opt_state, loss, _health = step(params, opt_state,
                                                    data)
    stats = train_stats(trainer)
    anatomy = stats["anatomy"]
    detail = {
        "chips": n_chips, "batch": batch, "seq": seq,
        "preset": preset, "steps": stats["goodput"]["steps"],
        "goodput": stats["goodput"],
        "anatomy_mean_ms": {k: (anatomy[k] or {}).get("mean")
                            for k in anatomy},
        "anomalies": stats["health"]["anomalies"],
        "loss": round(float(loss), 3) if loss is not None else None,
        "backend": jax.default_backend(),
    }
    emit({"metric": "train_goodput",
          "value": stats["goodput"]["ratio"], "unit": "ratio",
          "vs_baseline": None, "detail": detail})
    dw = anatomy["data_wait_ms"]
    for q in ("p50", "p99"):
        emit({"metric": f"train_data_wait_ms_{q}", "value": dw[q],
              "unit": "ms", "vs_baseline": None,
              "detail": {"count": dw["count"], "preset": preset,
                         "backend": jax.default_backend()}})


def main(args=None):
    args = args or parse_args()
    from ray_tpu._private.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    on_tpu = require_backend().platform == "tpu"
    n_chips = len(jax.devices())
    if args.chips:
        if args.chips > n_chips:
            raise SystemExit(f"bench: --chips {args.chips} but only "
                             f"{n_chips} device(s) are attached")
        n_chips = args.chips
    del _EMITTED[:]
    if args.decode:
        if args.spec_k > 0:
            main_decode_spec(args, on_tpu)
        else:
            main_decode(args, on_tpu)
        return _ledger_append(args)
    if args.traffic:
        main_traffic(args, on_tpu)
        return _ledger_append(args)
    if args.train:
        main_train_watch(args, on_tpu)
        return _ledger_append(args)
    if args.mesh == "tensor":
        raise SystemExit("--mesh tensor is a serve layout; combine it "
                         "with --decode or --traffic (train layouts: "
                         "data, fsdp, data_fsdp)")
    seq = 1024
    # b24 + mlp_only remat measured best on v5e by a builder's run on
    # 2026-07-31 (91,965 tok/s/chip, MFU 0.3486, vs b32/full-remat
    # 90,595/0.3434; not measured on the current tree); flash fwd bwd
    # recompute is skipped, attention un-rematted (O(T) flash
    # residuals).  mlp_only applies only at the DEFAULT batch:
    # user-overridden batches run full remat unless --remat says
    # otherwise (b32+mlp_only was a measured compile failure — untested
    # combos must not be implied).
    batch = args.batch or (24 * n_chips if on_tpu else 2)
    remat_policy = args.remat or ("mlp_only" if not args.batch
                                  else "full")
    cfg_kw = {}
    if args.ce_impl:
        cfg_kw["ce_impl"] = args.ce_impl
    if args.flash_resident:
        cfg_kw["flash_resident"] = args.flash_resident
    with _maybe_profile(args.profile):
        if on_tpu:
            tok_s_chip, mfu, final_loss, n_chips, cost = time_config(
                batch, seq=seq, n_steps=args.steps or 20,
                preset=args.preset or "gpt2", mesh=args.mesh,
                n_devices=args.chips, remat_policy=remat_policy,
                **cfg_kw)
        else:
            # the caller pinned the CPU: the same program at toy size,
            # to exercise the control flow (and, with --chips over
            # virtual host devices, the shardings) — not a measurement
            seq, remat_policy = 128, "full"
            if args.chips:
                batch = args.batch or max(2 * n_chips, 4)
            tok_s_chip, mfu, final_loss, n_chips, cost = time_config(
                batch, seq=seq, n_steps=args.steps or 2,
                preset=args.preset or "tiny", mesh=args.mesh,
                n_devices=args.chips, use_flash=False, **cfg_kw)
    # compiler cross-check: when XLA's own FLOP count disagrees with
    # the hand-counted 6·N·D by >5%, the hand count (and therefore the
    # headline MFU) is suspect — typically a model refactor changed the
    # arithmetic (attention share, remat recompute) under the formula.
    model_flops = cost.get("model_flops")
    xla_flops = cost.get("xla_flops")
    if model_flops and xla_flops:
        rel = abs(xla_flops - model_flops) / model_flops
        if rel > 0.05:
            sys.stderr.write(
                f"bench: WARNING hand-counted FLOPs diverge from "
                f"cost_analysis by {rel:.1%} (model_flops="
                f"{model_flops:.3e} vs xla_flops={xla_flops:.3e}/step)"
                f" — trust mfu_xla, re-derive the 6*N*D formula\n")
    emit({
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip"
                  if on_tpu else "gpt2_tiny_cpu_smoke_tokens_per_sec",
        "value": round(tok_s_chip, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / BASELINE_MFU, 3),
        "detail": {"chips": n_chips, "batch": batch, "seq": seq,
                   # effective layout: data_fsdp degrades to pure data
                   # on odd chip counts (fsdp axis of 1) — record what
                   # actually ran, not what was asked for
                   "mesh": ("data" if args.mesh == "data_fsdp"
                            and n_chips % 2 else args.mesh),
                   "mfu": round(mfu, 4),
                   # the compiler's own numbers next to the hand count
                   # (mfu_xla is the roofline MFU from cost_analysis
                   # FLOPs)
                   "model_flops": model_flops,
                   "xla_flops": xla_flops,
                   "mfu_xla": (round(cost["mfu_xla"], 4)
                               if cost.get("mfu_xla") else None),
                   "peak_hbm_bytes": cost.get("peak_hbm_bytes"),
                   "compile_seconds": cost.get("compile_seconds"),
                   "loss": round(final_loss, 3),
                   "remat_policy": remat_policy,
                   "ce_impl": args.ce_impl or "dense",
                   "flash_resident": args.flash_resident or "auto",
                   "backend": jax.default_backend()},
    })
    _ledger_append(args)


if __name__ == "__main__":
    sys.exit(main())
