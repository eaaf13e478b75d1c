"""Autoregressive decoding for the GLM-5 family: latent attention over
the positions a learned indexer selects, out of a cache of latents,
dense or paged.

The cache contract of decode_common with the latent TRIPLE: per token
and layer the latent ``ckv`` and the rotary key ``kpe`` that
models/kimi_k2_decode.py keeps, and the indexer's key ``kidx`` (after
its LayerNorm and rotary, ONE for all index heads):

  ckv  : (L, B, S, kv_lora_rank)     dense   (L, blocks, bs, kv_lora_rank)
  kpe  : (L, B, S, qk_rope_dim)              (L, blocks, bs, qk_rope_dim)
  kidx : (L, B, S, index_head_dim)           (L, blocks, bs, index_head_dim)

(1,408 B a token a layer in bf16 at the published widths), through
`PagedKV` as every family's per-position tensors go: every program
writes all three, a prefix's blocks share all three, and the attention
itself never reads ``kidx``.  The paths:

  * a decode step scores the row's WHOLE context from ``kidx`` (a
    gathered view of the index pool) and attends ABSORBED over the
    ``index_topk`` positions of highest score alone.  On the chip a
    paged cache is attended where it lies (since PR 59): the selection
    is a mask (`dsa.select_mask`), and ops/mla_paged_decode.py's walk
    of the row's block table, one Pallas call a layer
    (kimi_k2_decode.attend_paged), takes it beside its causal mask,
    the rotary keys re-laid once a step (``rotary_lanes``): every block
    of the row crosses HBM once, which is what a gather of 2,048 rows
    out of 8k spread evenly touches anyway, at ten times a gather's
    rate.  Off the chip, and over the dense cache, the step is ``jnp``
    and the oracle the kernel is held to: `dsa.select_top`'s positions,
    those latents and rotary keys gathered by (block table, offset),
    kimi_k2.attend_absorbed over the gathered rows.  Either way what
    the attention WEIGHS stops growing with the context, what the
    indexer reads does not;
  * a prefill scores blocks of queries against the keys they reach,
    finds each query's selection as a mask (`dsa.select_prefill`) and
    attends EXPANDED blockwise under it (kimi_k2_decode
    .attend_blockwise), in ``jnp``: every (query, key) pair is scored
    and masked, none is skipped.

A context of at most ``index_topk`` positions selects everything, and
the same paths then give dense latent attention's result.

``cache["experts"]`` holds the expert layers' routing counters of the
LAST program (decode_common.EXPERT_COUNTERS) and ``cache["index"]`` the
selection's (decode_common.INDEX_COUNTERS).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import (INDEX, INDEX_COUNTERS, PagedKV,
                                          _block_of, _positions,
                                          _refuse_mesh, dense_layer_kv,
                                          generator, is_paged, slot_mask)
from ray_tpu.models.experts import _with_counters
from ray_tpu.models.glm_dsa import GlmDsaConfig, block, selection
from ray_tpu.models.kimi_k2 import (attend_absorbed, attend_expanded,
                                    walk_layers)
from ray_tpu.models.kimi_k2_decode import attend_blockwise, attend_paged
from ray_tpu.models.layers import embed, lm_logits
from ray_tpu.ops import dsa
from ray_tpu.ops.mla_paged_decode import rotary_lanes

__all__ = ["glm_dsa_init_cache", "glm_dsa_init_paged_cache",
           "glm_dsa_prefill", "glm_dsa_paged_prefill",
           "glm_dsa_decode_step", "glm_dsa_generate"]


def _tensors(cfg: GlmDsaConfig, *lead: int):
    return {name: jnp.zeros((cfg.n_layer, *lead, width), cfg.dtype)
            for name, width in (("ckv", cfg.kv_lora_rank),
                                ("kpe", cfg.qk_rope_dim),
                                ("kidx", cfg.index_head_dim))}


def _vectors(batch: int):
    return dict(_positions(batch),
                **{INDEX: jnp.zeros((len(INDEX_COUNTERS),), jnp.float32)})


def glm_dsa_init_cache(cfg: GlmDsaConfig, batch: int,
                       mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (L, B, S, width) latents, rotary keys and index
    keys, position vectors, the last program's counters."""
    _refuse_mesh("glm_dsa", mesh)
    return dict(_tensors(cfg, batch, cfg.max_seq), **_vectors(batch))


def glm_dsa_init_paged_cache(cfg: GlmDsaConfig, batch: int, *,
                             num_blocks: int, block_size: int,
                             mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: three (L, num_blocks, block_size, width) pools
    and per-row block tables."""
    _refuse_mesh("glm_dsa", mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    return dict(_tensors(cfg, num_blocks, block_size),
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                **_vectors(batch))


@jax.named_scope(scopes.ATTN_INDEX)
def _with_index(cache, cfg: GlmDsaConfig, reachable):
    """`cache` with the program's `INDEX_COUNTERS` under
    `decode_common.INDEX`, from how many positions each of its queries
    could reach, `reachable` (...) int32 (0: a pad column, a row without
    a sequence): every layer selects for every query."""
    cache[INDEX] = cfg.n_layer * jnp.stack([
        jnp.sum(jnp.minimum(reachable, cfg.index_topk)),
        jnp.sum(reachable)]).astype(jnp.float32)
    return cache


def glm_dsa_prefill(params, tokens: jnp.ndarray, cfg: GlmDsaConfig, *,
                    lengths: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache
    (kimi_k2_decode.kimi_k2_prefill has the contract): every query's
    selection over a whole (T0, T0) score matrix, so for prompts that
    short (the parity oracle; the engine's prompts go through
    `glm_dsa_paged_prefill`)."""
    B, T0 = tokens.shape
    cache = glm_dsa_init_cache(cfg, B)
    col = jnp.arange(T0, dtype=jnp.int32)
    if lengths is None:
        start = jnp.zeros((B,), jnp.int32)
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
    real = col[None, :] >= start[:, None]                    # (B, T0)
    positions = jnp.maximum(col[None, :] - start[:, None], 0)
    mask = (col[None, :, None] >= col[None, None, :]) \
        & real[:, None, :]                                   # (B, T, S=T)
    x = embed(params, tokens, cfg)

    def layer(x, carry, p, lidx):
        new = []

        def attend(q, ckv, kpe, qi, w, kidx):
            new.extend((ckv, kpe, kidx))
            return attend_expanded(q, ckv, kpe, p["attn"],
                                   selection(qi, w, kidx, mask, cfg), cfg)

        x, stats = block(x, p, cfg, positions, attend, valid=real)
        return x, carry, tuple(new), stats

    x, _, rows, stats = walk_layers(cfg, params, x, (), layer)
    with jax.named_scope(scopes.KV_POOL):
        for name, new in zip(("ckv", "kpe", "kidx"), rows):
            cache[name] = lax.dynamic_update_slice(cache[name], new,
                                                   (0, 0, 0, 0))
    cache.update(start=start, pos=jnp.full((B,), T0, jnp.int32))
    return lm_logits(x[:, -1], params, cfg), \
        _with_index(_with_counters(cache, cfg, stats), cfg,
                    jnp.sum(mask, axis=-1, dtype=jnp.int32))


def glm_dsa_paged_prefill(params, cache, tokens: jnp.ndarray,
                          cfg: GlmDsaConfig, *, row_bt: jnp.ndarray,
                          prefix_len, n_tail, slot
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (gpt2_decode.paged_prefill has the contract): tokens (1, Tt)
    RIGHT-aligned tail of `n_tail` real columns after `prefix_len`
    tokens whose latents AND index keys are resident in `row_bt`'s
    blocks: the tail's queries score and select over prefix and tail
    alike.  Returns (logits (padded_vocab,) of the last real column,
    cache)."""
    _, Tt = tokens.shape
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad
    logical = prefix_len + col - pad               # position iff real
    reach = jnp.where(real, logical, -1)
    # pad columns MUST be masked writes (slot max_seq): their logical
    # index can alias a live prefix slot
    pkv = PagedKV(cache, row_bt[None],
                  jnp.where(real, logical, cfg.max_seq)[None], whole=True)
    positions = jnp.maximum(logical, 0)[None]
    qb, kb = _block_of(cfg, Tt), _block_of(cfg, cfg.max_seq)
    x = embed(params, tokens, cfg)

    def layer(x, pools, p, lidx):
        def attend(q, ckv, kpe, qi, w, kidx):
            nonlocal pools
            pools, (cview, rview, iview) = pkv.attend(lidx, pools, ckv,
                                                      kpe, kidx)
            keep = dsa.select_prefill(qi[0], w[0], iview[0], reach,
                                      cfg.index_topk, qb, kb)
            return attend_blockwise(q[0], cview[0], rview[0], p["attn"],
                                    logical, real, cfg, selected=keep)[None]

        x, stats = block(x, p, cfg, positions, attend, valid=real[None])
        return x, pools, (), stats

    x, pools, _, stats = walk_layers(cfg, params, x, pkv.pools, layer)
    # (as eight equal rows: kimi_k2_decode.kimi_k2_paged_prefill has why)
    logits = lm_logits(jnp.broadcast_to(x[0, -1], (8, cfg.d_model)),
                       params, cfg)[0]
    out = pkv.commit(pools)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, _with_index(_with_counters(out, cfg, stats), cfg,
                               reach + 1)


@jax.named_scope(scopes.KV_POOL)
def _selected_rows(rows, lidx, block_tables, idx, bs, own, fresh):
    """`dsa.gather_selected`, with the step's own new row `fresh`
    (B, 1, width) where the selection names the row's own slot (`own`
    (B, K)): the pool gets that row after the layers' scan."""
    return jnp.where(own[..., None], fresh.astype(rows.dtype),
                     dsa.gather_selected(rows, lidx, block_tables, idx, bs))


def glm_dsa_decode_step(params, cache, tokens, cfg: GlmDsaConfig
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    A row with ``pos == 0`` holds no sequence (kimi_k2_decode
    .kimi_k2_decode_step has what that means).

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    paged = is_paged(cache)
    # what the program can see of its input picks the path, as
    # kimi_k2_decode_step's: a paged cache on the chip is walked where
    # it lies under the selection's mask; the CPU gathers the selected
    # rows and keeps the jnp path, the parity oracle
    in_place = paged and jax.default_backend() == "tpu"
    pos, start = cache["pos"], cache["start"]
    rows = jnp.arange(B)
    with jax.named_scope(scopes.ATTN_INDEX):
        # the row's new position competes with the ones before it
        ok = slot_mask(start, pos + 1, cfg.max_seq)             # (B, S)
    pkv = PagedKV(cache, cache["block_tables"], pos[:, None],
                  whole=True) if paged else None
    if in_place:
        with jax.named_scope(scopes.KV_POOL):
            # once for all layers: the pools are read-only in the scan
            rope = rotary_lanes(cache["kpe"])
    elif paged:
        # once for all layers too
        rope_rows = dsa.pool_rows(cache["kpe"])
    x = embed(params, tokens, cfg)[:, None]                     # (B,1,d)

    def layer(x, pools, p, lidx):
        new = []

        def attend(q, ckv, kpe, qi, w, kidx):
            if paged:
                new.extend((ckv, kpe, kidx))             # (B, 1, width)
                bt = cache["block_tables"]
                # the pools are read-only in the scan: the step's rows
                # land after it (PagedKV.commit)
                scores = dsa.index_scores_step(qi[:, 0], w[:, 0], pools[2],
                                               lidx, bt, pos, kidx[:, 0])
            if in_place:
                return attend_paged(
                    q, pools[0], rope, cache, lidx, p["attn"], (ckv, kpe),
                    cfg, selected=dsa.select_mask(scores, ok,
                                                  cfg.index_topk))
            elif paged:
                idx, valid = dsa.select_top(scores, ok, cfg.index_topk)
                with jax.named_scope(scopes.ATTN_INDEX):
                    own = idx == pos[:, None]
                picked = (
                    _selected_rows(dsa.pool_rows(pools[0]), lidx, bt, idx,
                                   pkv.bs, own, ckv),
                    _selected_rows(rope_rows, lidx, bt, idx, pkv.bs, own,
                                   kpe))
            else:
                with jax.named_scope(scopes.KV_POOL):
                    views = tuple(
                        held.at[rows, pos].set(row[:, 0])
                        for held, row in zip(dense_layer_kv(cache, lidx),
                                             (ckv, kpe, kidx)))
                new.extend(views)
                idx, valid = dsa.select_top(
                    dsa.index_scores(qi, w, views[2])[:, 0], ok,
                    cfg.index_topk)
                with jax.named_scope(scopes.KV_POOL):
                    picked = tuple(
                        jnp.take_along_axis(view, idx[..., None], axis=1)
                        for view in views[:2])
            return attend_absorbed(q, *picked, p["attn"], valid[:, None],
                                   cfg)

        x, stats = block(x, p, cfg, (pos - start)[:, None], attend,
                         valid=(pos > 0)[:, None])
        return x, pools, tuple(new), stats

    x, pools, new, stats = walk_layers(
        cfg, params, x, pkv.pools if paged else (), layer)
    logits = lm_logits(x[:, 0], params, cfg)
    out = pkv.commit(pools, *new) if paged \
        else dict(cache, ckv=new[0], kpe=new[1], kidx=new[2])
    with jax.named_scope(scopes.KV_POOL):
        # a row without a sequence stays one (kimi_k2_decode_step)
        out["pos"] = jnp.where(pos > 0, pos + 1, 0)
    return logits, _with_index(_with_counters(out, cfg, stats), cfg,
                               jnp.where(pos > 0, pos + 1 - start, 0))


#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle
glm_dsa_generate = generator(glm_dsa_prefill, glm_dsa_decode_step)
