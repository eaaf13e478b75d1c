"""LM serving: the model zoo's KV-cache decoders behind a serve
deployment.

No reference analog module (the reference serves user torch models);
this packages the composition its users hand-roll — model init or
checkpoint load, jitted prefill/decode programs, request batching —
so `serve.run(build_llm_deployment(...).bind())` is a working LM
endpoint for either decoder family (gpt2 / llama).

Two schedulers:

  * "batch" — @serve.batch micro-batching: concurrent requests are
    collected into one `generate` call and run TO COMPLETION together.
    Ragged prompt lists are LEFT-padded before stacking (the decode
    cache contract) and the pads trimmed from each returned row;
    equal-length batches keep the pad-free fast path (flash-eligible
    prefill).
  * "continuous" — slot-based continuous batching: a fixed pool of
    `max_slots` KV-cache rows.  Each admitted request gets ONE batched
    prefill dispatch into a free slot; all active slots then share one
    jitted decode step per token.  Finished sequences free their slot
    immediately and queued requests are admitted mid-flight — short
    requests are never held hostage by long ones, the failure mode of
    stack-and-pray fixed batching.  Prompt lengths are padded up to
    `prefill_bucket` multiples so the prefill program compiles once
    per bucket, not once per length.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import numpy as np

from ray_tpu.models import families
from ray_tpu.models.decode_common import SamplingParams
from ray_tpu.serve.api import deployment
from ray_tpu.serve.batching import batch as _batch
from ray_tpu.serve.engine import EngineBase, LLMEngine
from ray_tpu.serve.engine_programs import _jitted_engine_fns

__all__ = ["build_llm_deployment", "EngineOptions", "SpecConfig",
           "SamplingParams", "LLMEngine", "BatchLLM",
           "_jitted_engine_fns"]


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding knob for the continuous engine (round 11).

    draft: "ngram" (host-side zero-weight n-gram draft built from each
    request's own history) or "<family>:<preset>" (a small draft
    MODEL, e.g. "gpt2:nano" — its decode steps run in one jitted
    k+1-step scan per round).  k drafted tokens are verified per slot
    per round by ONE target verify dispatch, so at acceptance rate a
    the target runs ~1/(1 + a*k) dispatches per emitted token.
    draft_seed: PRNG seed for the draft model's init (None → the
    engine seed, so draft == target arch + preset + seed gives the
    perfectly aligned draft the CPU benches use).

    Frozen + hashable: part of the jitted-program cache key, so
    engines differing in k or draft can never alias one compiled
    program."""
    draft: str = "ngram"
    k: int = 4
    ngram_order: int = 2
    draft_seed: Optional[int] = None

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec k must be >= 1, got {self.k}")
        if self.draft != "ngram":
            parts = self.draft.split(":")
            if len(parts) != 2 or \
                    families.cache_kind(parts[0]) != families.KV:
                can = "|".join(
                    name for name in families.FAMILIES
                    if families.cache_kind(name) == families.KV)
                raise ValueError(
                    f"spec draft must be 'ngram' or "
                    f"'<family>:<preset>' with family {can}, "
                    f"got {self.draft!r}")
        if self.ngram_order < 1:
            raise ValueError(
                f"ngram_order must be >= 1, got {self.ngram_order}")


def _refuse_for_recurrent(family, *, spec_decode, kv_host_tier_bytes,
                          role, mesh) -> None:
    """What cannot carry a cache that is not plain K/V yet refuses,
    loudly, before anything is built.  A recurrent state: a spec-decode
    rewind moves `pos` back and the state has no earlier value to go
    back to; the host tier and the handoff move K/V blocks and would
    leave the state behind; the state has no sharding rule.  A latent
    pool: the family has no verify program; the host tier and the
    handoff move rows of ONE shape where the pool's two tensors differ;
    a latent has no heads axis to shard.  A window layer's ring is
    per-slot state as the recurrent one is, and is refused alike."""
    kind = families.cache_kind(family)
    asked = {"spec_decode": spec_decode is not None,
             "kv_host_tier_bytes": kv_host_tier_bytes is not None,
             f"role={role!r}": role != "both", "mesh": mesh is not None}
    for option, on in asked.items():
        if on:
            raise ValueError(
                f"family {family!r} keeps a {kind} cache "
                f"({families.CACHE_HOLDS[kind]}), "
                f"which {option} cannot carry yet: refused")


@dataclasses.dataclass(frozen=True)
class EngineOptions:
    """The parameters of `build_llm_deployment` (which documents each),
    checked against one another on construction: what an engine
    instance reads as `self.opt`."""
    family: str = "gpt2"
    preset: str = "nano"
    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    stop_sequences: Any = None
    eos_id: Optional[int] = None
    max_batch_size: int = 8
    batch_wait_timeout_s: float = 0.05
    checkpoint_path: Optional[str] = None
    seed: int = 0
    num_replicas: int = 1
    scheduler: str = "batch"
    max_slots: int = 4
    prefill_bucket: int = 16
    kv_layout: str = "dense"
    kv_block_size: int = 16
    kv_num_blocks: Optional[int] = None
    prefill_chunk_tokens: Optional[int] = None
    kv_host_tier_bytes: Optional[int] = None
    admission_policy: Any = None
    slo: Any = None
    mesh: Any = None
    spec_decode: Optional[SpecConfig] = None
    role: str = "both"
    handoff_staged: bool = False
    config_overrides: Optional[Dict[str, Any]] = None

    def __post_init__(self):
        if self.family not in families.FAMILIES:
            raise ValueError(f"unknown LM family {self.family!r}")
        if families.cache_kind(self.family) in families.CACHE_HOLDS:
            _refuse_for_recurrent(
                self.family, spec_decode=self.spec_decode,
                kv_host_tier_bytes=self.kv_host_tier_bytes,
                role=self.role, mesh=self.mesh)
        if self.scheduler not in ("batch", "continuous"):
            raise ValueError(f"unknown scheduler {self.scheduler!r} "
                             f"(expected 'batch' or 'continuous')")
        if self.kv_layout not in ("dense", "paged"):
            raise ValueError(f"unknown kv_layout {self.kv_layout!r} "
                             f"(expected 'dense' or 'paged')")
        if self.kv_layout == "paged" and self.scheduler != "continuous":
            raise ValueError("kv_layout='paged' requires "
                             "scheduler='continuous' (the block pager "
                             "lives in the continuous engine)")
        if self.prefill_chunk_tokens is not None:
            if self.kv_layout != "paged":
                raise ValueError(
                    "prefill_chunk_tokens requires kv_layout='paged' "
                    "(chunks fill KV blocks incrementally through "
                    "paged_prefill; dense keeps one-shot prefill as the "
                    "bit-exactness oracle)")
            if self.prefill_chunk_tokens < 1 \
                    or self.prefill_chunk_tokens % self.kv_block_size:
                raise ValueError(
                    f"prefill_chunk_tokens={self.prefill_chunk_tokens} "
                    f"must be a positive multiple of kv_block_size="
                    f"{self.kv_block_size} (chunks must end on block "
                    "boundaries so prior chunks are resident prefix "
                    "blocks)")
        if self.kv_host_tier_bytes is not None:
            if self.kv_layout != "paged":
                raise ValueError(
                    "kv_host_tier_bytes requires kv_layout='paged' (the "
                    "host tier spills and restores the pager's KV "
                    "blocks; dense rows are never evicted)")
            if int(self.kv_host_tier_bytes) <= 0:
                raise ValueError(
                    f"kv_host_tier_bytes={self.kv_host_tier_bytes} must be a "
                    "positive byte budget")
        if self.role not in ("both", "prefill", "decode"):
            raise ValueError(f"unknown role {self.role!r} (expected 'both', "
                             "'prefill', or 'decode')")
        if self.role != "both":
            if self.scheduler != "continuous":
                raise ValueError(
                    f"role={self.role!r} requires scheduler='continuous' "
                    "(the handoff parks/admits through the slot-pool "
                    "engine loop)")
            if self.kv_layout != "paged":
                raise ValueError(
                    f"role={self.role!r} requires kv_layout='paged' (the "
                    "handoff moves block rows between pagers; dense rows "
                    "have no block-granular identity to hand off)")
        if self.handoff_staged and self.role == "both":
            raise ValueError(
                "handoff_staged only applies to split roles "
                "(role='prefill' exports through host staging; a "
                "monolithic engine never hands off)")
        if self.mesh is not None and self.scheduler != "continuous":
            raise ValueError("mesh-sharded serving requires "
                             "scheduler='continuous' (the batch scheduler "
                             "is single-device)")
        if self.spec_decode is not None:
            if not isinstance(self.spec_decode, SpecConfig):
                raise ValueError("spec_decode must be a SpecConfig, got "
                                 f"{type(self.spec_decode).__name__}")
            if self.scheduler != "continuous":
                raise ValueError("spec_decode requires "
                                 "scheduler='continuous' (speculation "
                                 "lives in the slot-pool engine loop)")
        if self.slo is not None:
            from ray_tpu.serve.slo import SLOConfig
            if not isinstance(self.slo, SLOConfig):
                raise ValueError("slo must be a serve.slo.SLOConfig, got "
                                 f"{type(self.slo).__name__}")
            if self.scheduler != "continuous":
                raise ValueError("slo requires scheduler='continuous' "
                                 "(the burn-rate watchdog runs from the "
                                 "slot-pool engine loop)")
        self.default_sp     # SamplingParams checks the knobs
        if any(len(s) == 0 for s in self.stop_seqs):
            raise ValueError("empty stop sequence")

    @functools.cached_property
    def default_sp(self) -> SamplingParams:
        """The engine's default per-request params: requests that don't
        override sample through the fused programs this bakes in."""
        return SamplingParams(temperature=self.temperature,
                              top_k=self.top_k, top_p=self.top_p)

    @functools.cached_property
    def stop_seqs(self) -> tuple:
        """`stop_sequences` as tuples of ints."""
        return tuple(
            tuple(int(t) for t in np.asarray(s, np.int64).reshape(-1))
            for s in (self.stop_sequences or ()))


class BatchLLM(EngineBase):
    """The "batch" scheduler: @serve.batch over (possibly ragged)
    lists, one fused `generate` per micro-batch."""

    def _init_scheduler(self, fam) -> None:
        import jax

        opt = self.opt
        sampling = dict(max_new_tokens=opt.max_new_tokens,
                        temperature=opt.temperature, top_k=opt.top_k,
                        top_p=opt.top_p)
        # the batch scheduler keeps no cache between calls: its build
        # has no ``cache`` phase
        with self._build("programs"):
            self._generate = jax.jit(
                lambda p, toks, k: fam.generate(
                    p, toks, self.cfg, key=k, **sampling))
            self._generate_ragged = jax.jit(
                lambda p, toks, lens, k: fam.generate(
                    p, toks, self.cfg, lengths=lens, key=k, **sampling))
        # this instance's micro-batch queue, sized by its options
        self._batched = _batch(
            max_batch_size=opt.max_batch_size,
            batch_wait_timeout_s=opt.batch_wait_timeout_s)(
                self._call_batch)

    async def _call_batch(self, prompts):
        import jax
        import jax.numpy as jnp

        self._rng, k = jax.random.split(self._rng)
        # host-side prompt normalization (python ints, no device fetch)
        # graftcheck: disable=blocking-call-in-async(host-side int normalization)
        arrs = [np.asarray(p, np.int32).reshape(-1) for p in prompts]
        lens = [int(a.shape[0]) for a in arrs]
        t0 = max(lens)
        if min(lens) == t0:
            # equal-length fast path: no pads, flash-eligible
            toks = jnp.asarray(np.stack(arrs), jnp.int32)
            out = self._generate(self.params, toks, k)
            # the batch is done on device and callers need host arrays
            # graftcheck: disable=blocking-call-in-async(deliberate result fetch)
            return [np.asarray(row) for row in out]
        padded = np.zeros((len(arrs), t0), np.int32)
        for i, a in enumerate(arrs):
            padded[i, t0 - lens[i]:] = a
        out = self._generate_ragged(
            self.params, jnp.asarray(padded),
            jnp.asarray(lens, jnp.int32), k)
        # trim the left pads: each caller sees prompt+continuation
        # graftcheck: disable=blocking-call-in-async(deliberate result fetch)
        return [np.asarray(row)[t0 - n:] for row, n in zip(out, lens)]

    async def __call__(self, prompt, sampling=None):
        max_new = self.opt.max_new_tokens
        if sampling is not None:
            raise ValueError(
                "per-request sampling requires "
                "scheduler='continuous' (the batch scheduler runs "
                "one fused generate per micro-batch)")
        # request-level telemetry wraps the @serve.batch queue so
        # the recorded latency includes the batch-collection wait
        # prompt is a host-side list; its length moves no device data
        # graftcheck: disable=blocking-call-in-async(host-side length probe)
        n_prompt = int(np.asarray(prompt).reshape(-1).shape[0])
        rec = self._telemetry.record_enqueue(n_prompt)
        if n_prompt == 0 or n_prompt + max_new > self.cfg.max_seq:
            # pre-validate BEFORE batching: an oversized prompt
            # used to blow up the whole micro-batch from inside
            # generate (and bypassed the rejection metrics lane)
            self._telemetry.record_reject(
                rec, reason=f"prompt length {n_prompt}",
                label="oversized")
            raise ValueError(
                f"prompt length {n_prompt} invalid for "
                f"max_seq={self.cfg.max_seq} with "
                f"max_new_tokens={max_new}")
        try:
            out = await self._batched(prompt)
        except Exception as e:  # noqa: BLE001 - caller sees it too
            self._telemetry.record_error(rec, error=repr(e))
            raise
        self._telemetry.record_finish(rec, n_tokens=max_new)
        return out


def build_llm_deployment(family: str = "gpt2", preset: str = "nano",
                         *, max_new_tokens: int = 16,
                         temperature: float = 0.0,
                         top_k: int = 0, top_p: float = 1.0,
                         stop_sequences=None,
                         eos_id: Optional[int] = None,
                         max_batch_size: int = 8,
                         batch_wait_timeout_s: float = 0.05,
                         checkpoint_path: Optional[str] = None,
                         seed: int = 0, num_replicas: int = 1,
                         scheduler: str = "batch",
                         max_slots: int = 4,
                         prefill_bucket: int = 16,
                         kv_layout: str = "dense",
                         kv_block_size: int = 16,
                         kv_num_blocks: Optional[int] = None,
                         prefill_chunk_tokens: Optional[int] = None,
                         kv_host_tier_bytes: Optional[int] = None,
                         admission_policy=None,
                         slo=None,
                         mesh=None,
                         spec_decode: Optional[SpecConfig] = None,
                         role: str = "both",
                         handoff_staged: bool = False,
                         config_overrides: Optional[Dict[str, Any]]
                         = None):
    """A serve Deployment generating continuations for int32
    token-prompt arrays (1-D per request; ragged lengths welcome —
    each caller gets back its own prompt + continuation, pads
    trimmed).

    family: "gpt2" | "llama" | "jamba" (a row of `_FAMILIES`); preset:
    a model-zoo preset name.  A family whose cache is
    "kv+recurrent" (jamba: Mamba layers keep one state per slot beside
    the K/V pool of its attention layers) is served by the same engine;
    with the paged layout a prompt skips a resident prefix only as far
    as a snapshot of that state reaches (kv_pager.StateSnapshots), and
    spec_decode, kv_host_tier_bytes, a split role and mesh are refused
    for it, since none can carry the state yet.
    scheduler: "batch" (@serve.batch fixed micro-batches) or
    "continuous" (slot pool of `max_slots` KV rows with mid-flight
    admission; `prefill_bucket` bounds prefill recompiles).
    kv_layout: "dense" (per-slot rows, the parity oracle) or "paged"
    (shared block pool + per-row block tables managed by
    serve/kv_pager.py — prompt prefixes resident from earlier requests
    are reused instead of re-prefilled, with copy-on-write forks at
    shared write boundaries).  kv_block_size sets the block token
    granularity; kv_num_blocks the pool size (default: enough for
    every slot plus one sequence of prefix-cache headroom).
    prefill_chunk_tokens: chunked streaming prefill (paged layout
    only; dense keeps one-shot prefill as the bit-exactness oracle).
    A prompt whose unmatched tail exceeds N tokens is admitted as a
    sequence of block-aligned prefill chunks interleaved with decode
    waves — the engine loop alternates `decode wave → at most one
    chunk of pending prefill → decode wave`, with round-robin
    fairness over chunking slots so one huge prompt cannot consume
    consecutive chunk windows.  Each chunk is a call to the existing
    paged_prefill program with prefix_len = tokens already filled
    (prior chunks are literally resident prefix blocks), so chunked
    output is bit-identical to one-shot prefill by construction and
    the program compiles once per prefill_bucket-padded chunk shape.
    Must be a positive multiple of kv_block_size.  None (default)
    keeps one-shot prefill.
    kv_host_tier_bytes: tiered host-RAM KV cache (paged layout only;
    serve/kv_tier.py).  When set, a prefix block the pager's LRU
    eviction claims is spilled device→host into a byte-budgeted
    LRU store under its content-addressed key, and an admission whose
    HBM prefix match falls short probes that store second-chance: a
    hit re-installs the block via one H2D copy + block-table splice
    and bumps prefix_len so paged_prefill skips those tokens — the
    effective prefix cache grows beyond HBM and re-admitted prefixes
    cost a copy instead of a re-prefill (outputs stay bit-identical
    to the dense oracle; the restore rows ARE the rows prefill would
    write).  Surfaced as engine_stats()["kv_tier"], tracebus
    `kv.fetch` spans, and the `kv_fetch_ms` critical-path component.
    None (default) keeps plain discard-on-evict.
    admission_policy: a serve.batching.AdmissionPolicy closing the
    telemetry loop — requests are load-shed with OverloadedError when
    its queue-depth / queue-wait / TTFT gates trip.
    slo: a serve.slo.SLOConfig (continuous scheduler only) turning the
    telemetry stream into multi-window burn rates —
    engine_stats()["slo"], serve_slo_* metrics, and an anomaly
    watchdog that postmortem-dumps the engine's flight record
    (_private/flightrec.py) on burn-rate breaches and recompile
    storms.  Without it engine_stats()["slo"] is None; the flight
    recorder itself is always on (RAYTPU_FLIGHTREC=0 disables).
    mesh: a `jax.sharding.Mesh` to tensor-parallelise the engine over
    (continuous scheduler only).  Params and the KV pool are committed
    to the mesh under parallel.sharding.DECODE_RULES — attention
    heads, MLP hidden, lm-head vocab, and the pool's KV-head dim split
    over the `tensor` axis (dims the degree doesn't divide replicate);
    the committed input shardings propagate through the existing
    jitted programs, so one pool step spans all chips.  Block tables
    and the BlockPager stay host-side and layout-agnostic.  None (the
    default) keeps today's single-device behaviour.
    top_k / top_p: engine-default nucleus knobs composed with
    `temperature` (jit-static, baked into the fused sample-included
    programs).  Continuous-scheduler callers may override per request
    with `handle.remote(prompt, sampling=SamplingParams(...))` — the
    engine routes those slots through a logits-returning twin program
    plus a per-SamplingParams jitted sampler, so the default hot path
    stays one fused dispatch.
    stop_sequences / eos_id: host-side stop matching on the GENERATED
    tokens (continuous scheduler): a slot whose tail matches any stop
    sequence (or whose last token == eos_id) finishes immediately,
    freeing its slot (and paged blocks) mid-flight for the next queued
    request — generation never burns the full max_new_tokens budget on
    a sequence that already ended.
    spec_decode: a SpecConfig enabling speculative decoding on the
    continuous engine — a draft (n-gram or small model) proposes k
    tokens per slot per round and ONE jitted target verify dispatch
    checks all k+1 positions, so at acceptance rate a the target runs
    ~1/(1 + a*k) dispatches per emitted token.  Greedy (temperature 0)
    spec output is bit-identical to the non-speculative engine.
    role: disaggregated prefill/decode serving (round 18).  "both"
    (default) is the monolithic engine.  "prefill" engines run the
    admission + prefill machinery only and PARK at the handoff: when a
    request's last chunk finishes, the filled KV block rows are
    exported (one fixed-shape kv_handoff_export gather) and the
    request's future resolves with a serve.batching.HandoffCursor
    instead of tokens — the fleet router forwards it to a decode
    replica.  "decode" engines accept those cursors through
    ``admit_prefilled``: fresh blocks are allocated, the rows land via
    one donated kv_handoff_install splice (block table + pos + start
    set in the same dispatch), and decoding resumes at the prefill
    replica's first token — bit-identical to the monolithic engine by
    construction.  Both split roles require scheduler='continuous'
    and kv_layout='paged'.
    handoff_staged: force the staged D2H→H2D handoff hop (the general
    cross-process path — export rows are pulled to host before the
    decode-side install) even when prefill and decode replicas share
    one process.  Default False keeps the same-process fast path,
    where the exported rows stay device-resident end to end.
    checkpoint_path: pickled param pytree (matching the family's init
    layout); absent → fresh init from `seed` (tests/demos)."""
    opt = EngineOptions(
        family=family, preset=preset, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, top_p=top_p,
        stop_sequences=stop_sequences, eos_id=eos_id,
        max_batch_size=max_batch_size,
        batch_wait_timeout_s=batch_wait_timeout_s,
        checkpoint_path=checkpoint_path, seed=seed,
        num_replicas=num_replicas, scheduler=scheduler,
        max_slots=max_slots, prefill_bucket=prefill_bucket,
        kv_layout=kv_layout, kv_block_size=kv_block_size,
        kv_num_blocks=kv_num_blocks,
        prefill_chunk_tokens=prefill_chunk_tokens,
        kv_host_tier_bytes=kv_host_tier_bytes,
        admission_policy=admission_policy, slo=slo, mesh=mesh,
        spec_decode=spec_decode, role=role,
        handoff_staged=handoff_staged,
        config_overrides=config_overrides)
    engine = LLMEngine if scheduler == "continuous" else BatchLLM
    # the deployed class is the scheduler's with these options bound
    bound = type("LLM", (engine,), {"opt": opt})
    return deployment(name=f"llm_{family}_{preset}",
                      num_replicas=num_replicas)(bound)
