"""The continuous engine's jitted programs (serve/engine.py runs them).

Which programs exist, the engine's default sampling baked into the
fused ones, what each donates, the shardings a donated cache is held
to, and the names the perf registry knows them by.  What the programs
compute is the model layer's: a `models.families.Family` supplies
prefill / paged_prefill / step / verify, and every operation on the
cache pytree is `models/decode_common.py`'s.
"""

from __future__ import annotations

import functools
import types
from typing import Any, Dict

from ray_tpu.models.decode_common import SamplingParams
from ray_tpu.models.families import PER_SLOT_STATE

# jax's compile cache is keyed by the jitted function OBJECT, so a
# fresh `jax.jit(closure)` per engine instance recompiles every
# program for every instance — pathological for test suites and
# notebooks that build many short-lived engines.  The continuous
# engine's programs depend only on (family fns, config, sampling
# config, kv layout, mesh, spec config + draft fns); configs /
# SamplingParams / SpecConfig are frozen dataclasses and jax Meshes
# are hashable by (axis names, device assignment), so equal-config
# engines can share ONE set of jitted callables and therefore one
# compile — while engines that differ in ANY closure input (layout,
# mesh, a sampling knob, spec k, the draft) get their own entries
# instead of aliasing a stale compiled program (round-11 regression:
# the key once carried only `temperature`, so a top_k change or a
# different spec k would silently reuse the old sampler).
_JIT_CACHE: Dict[Any, Any] = {}


def _jitted_engine_fns(family, cfg, sampling, kv_layout="dense",
                       mesh=None, spec=None, draft=None, draft_cfg=None):
    """Namespace of jitted programs for one engine identity (`family`
    a models.families.Family; `draft`, with its `draft_cfg`, the Family
    of a spec-decode draft MODEL):

      prefill / paged_prefill / pool_step  — fused sample-included
          programs (engine-default sampling baked in; the hot path
          stays one dispatch).  The paged prefills' last argument is
          the `state` a family with per-slot state is told (where the
          slot's state starts, the snapshot it leaves:
          LLMEngine._state_arg); a KV family is given None there
      prefill_raw / paged_prefill_raw / pool_logits — logits-returning
          twins for requests overriding SamplingParams (compiled only
          if such a request arrives)
      take_counters                        — a copy of the counter
          vectors a program left in the cache
          (decode_common.program_counters), queued behind that program:
          the copy outlives the cache's next donation and is read at
          the fence the program's tokens are read at
      admit / copy_block / clear_row / restore_state / install_blocks
      / save_block / kv_handoff_export / kv_handoff_install — pool
          bookkeeping: the cache operations of models/decode_common.py
      join_token                           — a prefill's first token
          into the tokens of the wave queued behind it

    Every program that takes the engine's cache and returns it
    CONSUMES it (donate_argnums): the result is the same buffers
    updated in place, the argument is dead once the call is made, and
    the caller rebinds (`self._cache = ...`).  Only `admit` (dense
    rows) and the read-only `save_block` / `kv_handoff_export` leave
    their cache argument alive.  Under a mesh the returned cache is
    pinned to the committed cache shardings, so the alias holds shard
    for shard.
      spec_verify                          — (spec only) ONE target
          dispatch verifying a (B, k+1) draft block, KV donated
      draft_propose                        — (model draft only) the
          k+1-step draft scan

    `sampling` is a SamplingParams (a bare float is accepted as
    temperature-only for backward compatibility).  The cache key
    carries the family's programs and the FULL sampling + spec
    identity."""
    if not isinstance(sampling, SamplingParams):
        sampling = SamplingParams(temperature=float(sampling))
    recurrent = family.cache_kind in PER_SLOT_STATE
    verify_fn = family.verify if spec is not None else None
    draft_fns = None if draft is None else (draft.prefill, draft.step,
                                            draft_cfg)
    key = (family.prefill, family.step, family.paged_prefill, recurrent,
           cfg, sampling, kv_layout, mesh, spec, verify_fn, draft_fns)
    cached = _JIT_CACHE.get(key)
    if cached is not None:
        return cached
    import jax
    from jax import lax

    from ray_tpu.models import decode_common as dc

    tail = dc.make_vocab_tail_mask(cfg)
    temperature = sampling.temperature
    top_k, top_p = sampling.top_k, sampling.top_p

    def pinned(cache):
        # a donated cache aliases its result only where both have one
        # sharding: hold the result to the shardings the engine
        # committed its cache to (partitioned_cache_init)
        if mesh is None:
            return cache
        return lax.with_sharding_constraint(
            cache, dc.cache_shardings(cache, mesh))

    def consuming(op):
        """A cache operation as the program whose result takes the
        donated cache's place (under the operation's own name)."""
        @functools.wraps(op)
        def program(cache, *args):
            return pinned(op(cache, *args))
        return program

    def prefill_sample(p, toks, lens, k):
        logits, cache = family.prefill(p, toks, cfg, lengths=lens)
        return dc.sample_token(logits, k, temperature, tail, top_k,
                               top_p), cache

    def prefill_raw(p, toks, lens):
        return family.prefill(p, toks, cfg, lengths=lens)

    def paged_prefill_raw(p, cache, toks, row_bt, prefix_len, n_tail,
                          slot, state):
        # only a recurrent family's prefill takes `state`
        told = {"state": state} if recurrent else {}
        logits, cache = family.paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot, **told)
        return logits[None], pinned(cache)

    def paged_prefill_sample(p, cache, toks, row_bt, prefix_len,
                             n_tail, slot, k, state):
        logits, cache = paged_prefill_raw(p, cache, toks, row_bt,
                                          prefix_len, n_tail, slot, state)
        return dc.sample_token(logits, k, temperature, tail, top_k,
                               top_p), cache

    def pool_step(p, cache, toks, k):
        logits, cache = family.step(p, cache, toks, cfg)
        return dc.sample_token(logits, k, temperature, tail, top_k,
                               top_p), pinned(cache)

    def pool_logits(p, cache, toks):
        logits, cache = family.step(p, cache, toks, cfg)
        return logits, pinned(cache)

    def join_token(toks, slot, tok):
        # a prefill's first token into a wave's tokens, on the device
        return lax.dynamic_update_slice(toks, tok.astype(toks.dtype), (slot,))

    def take_counters(counters):
        # a copy that outlives the cache's next donation
        return jax.tree.map(lambda c: c + 0, counters)

    def fork_block(cache, src, dst):
        return pinned(dc.copy_block(cache, src, dst))

    # perf observatory: the heavy programs report compiles / compiler
    # cost model / invoke walltimes to the process-wide registry under
    # stable names (sharded engines get their own so single- and
    # multi-chip cost models never mix)
    from ray_tpu._private.device_stats import get_registry

    registry = get_registry()
    shard = "serve.sharded_" if mesh is not None else "serve."
    n_dev = len(getattr(mesh, "devices", [[None]]).flat) \
        if mesh is not None else 1
    spec_verify = draft_propose = draft_prefill = None
    if spec is not None:
        verify_accept = dc.make_spec_verify(verify_fn, cfg,
                                            temperature=temperature,
                                            top_k=top_k, top_p=top_p)

        def verify(*args):
            out, n_acc, cache = verify_accept(*args)
            return out, n_acc, pinned(cache)

        # the target KV pool (arg 1) is donated: the verify round is
        # the engine's steady-state hot program and the old pool is
        # dead the moment the new one lands
        spec_verify = registry.instrument(
            shard + "spec_verify",
            jax.jit(verify, donate_argnums=(1,)), n_dev)
        if draft_fns is not None:
            d_prefill_fn, d_step_fn, d_cfg = draft_fns
            d_tail = dc.make_vocab_tail_mask(d_cfg)
            propose = dc.make_draft_propose(
                d_step_fn, d_cfg, spec.k, temperature=temperature,
                top_k=top_k, top_p=top_p,
                with_probs=temperature > 0.0)
            draft_propose = registry.instrument(
                shard + "spec_draft", jax.jit(propose), n_dev)

            def d_prefill(p, toks, lens, k):
                logits, cache = d_prefill_fn(p, toks, d_cfg, lengths=lens)
                return dc.sample_token(logits, k, temperature, d_tail,
                                       top_k, top_p), cache

            draft_prefill = jax.jit(d_prefill)
    fns = types.SimpleNamespace(
        prefill=registry.instrument(shard + "prefill",
                                    jax.jit(prefill_sample), n_dev),
        paged_prefill=registry.instrument(
            shard + "paged_prefill",
            jax.jit(paged_prefill_sample, donate_argnums=(1,)), n_dev),
        pool_step=registry.instrument(
            shard + "decode", jax.jit(pool_step, donate_argnums=(1,)),
            n_dev),
        prefill_raw=jax.jit(prefill_raw),
        paged_prefill_raw=jax.jit(paged_prefill_raw,
                                  donate_argnums=(1,)),
        pool_logits=jax.jit(pool_logits, donate_argnums=(1,)),
        admit=jax.jit(dc.admit),
        join_token=jax.jit(join_token),
        take_counters=jax.jit(take_counters),
        copy_block=jax.jit(fork_block, donate_argnums=(0,)),
        clear_row=jax.jit(consuming(dc.clear_row), donate_argnums=(0,)),
        restore_state=jax.jit(consuming(dc.restore_state),
                              donate_argnums=(0,)),
        install_blocks=jax.jit(consuming(dc.install_blocks),
                               donate_argnums=(0,)),
        save_block=jax.jit(dc.save_block),
        kv_handoff_export=registry.instrument(
            shard + "kv_handoff_export", jax.jit(dc.kv_handoff_export),
            n_dev),
        kv_handoff_install=registry.instrument(
            shard + "kv_handoff_install",
            jax.jit(consuming(dc.kv_handoff_install),
                    donate_argnums=(0,)), n_dev),
        spec_verify=spec_verify, draft_propose=draft_propose,
        draft_prefill=draft_prefill)
    _JIT_CACHE[key] = fns
    return fns
