"""Autoregressive decoding for the Kimi-K2 family: latent attention over
a cache of latents, dense or paged.

The cache contract of decode_common with the latent pair in K/V's
place: per token and layer ONE latent ``ckv`` (kv_lora_rank wide, after
its norm) and ONE rotary key ``kpe`` (after RoPE), shared by all heads:

  ckv : (L, B, S, kv_lora_rank)   dense   (L, blocks, bs, kv_lora_rank)
  kpe : (L, B, S, qk_rope_dim)            (L, blocks, bs, qk_rope_dim)

(1,152 B a token a layer in bf16 at the published widths, where K and V
of 64 heads would be 40,960), through `PagedKV` as every family's K/V
go.  Two attention paths read them (models/kimi_k2.py): a decode step
attends ABSORBED, over the latents themselves; a prefill up-projects
the latents it needs once and attends EXPANDED, blockwise over queries
and keys with a running softmax (`attend_blockwise`), as far as the
causal mask reaches and no further.

``cache["experts"]`` holds what the expert layers' routing did on this
chip in the LAST program (decode_common.EXPERT_COUNTERS).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import (PagedKV, _block_of, _positions,
                                          _refuse_mesh, dense_layer_kv,
                                          generator, is_paged, slot_mask)
from ray_tpu.models.experts import _with_counters
from ray_tpu.models.kimi_k2 import (KimiK2Config, attend_absorbed,
                                    attend_expanded, block, expand_keys,
                                    expand_latents, softmax_scale,
                                    walk_layers)
from ray_tpu.models.layers import embed, lm_logits
from ray_tpu.ops import mla_flash_prefill as flash
from ray_tpu.ops.mla_paged_decode import mla_paged_decode, rotary_lanes

__all__ = ["kimi_k2_init_cache", "kimi_k2_init_paged_cache",
           "kimi_k2_prefill", "kimi_k2_paged_prefill",
           "kimi_k2_prefill_attention",
           "kimi_k2_decode_step", "kimi_k2_generate"]


def _latent_tensors(cfg: KimiK2Config, *lead: int):
    return {"ckv": jnp.zeros((cfg.n_layer, *lead, cfg.kv_lora_rank),
                             cfg.dtype),
            "kpe": jnp.zeros((cfg.n_layer, *lead, cfg.qk_rope_dim),
                             cfg.dtype)}


def kimi_k2_init_cache(cfg: KimiK2Config, batch: int,
                       mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (L, B, S, width) latents and rotary keys, position
    vectors, the last program's expert counters."""
    _refuse_mesh("kimi_k2", mesh)
    return dict(_latent_tensors(cfg, batch, cfg.max_seq),
                **_positions(batch))


def kimi_k2_init_paged_cache(cfg: KimiK2Config, batch: int, *,
                             num_blocks: int, block_size: int,
                             mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: (L, num_blocks, block_size, width) pools and
    per-row block tables."""
    _refuse_mesh("kimi_k2", mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    return dict(_latent_tensors(cfg, num_blocks, block_size),
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                **_positions(batch))


@jax.named_scope(scopes.MLA)
def attend_blockwise(q, ckv, kpe, p, logical, real, cfg: KimiK2Config,
                     selected=None):
    """One sequence's expanded attention without its score matrix.

    q (T, H, qk) at positions `logical` (T,), `real` (T,) False on pad
    columns; ckv (S, c), kpe (S, r) the sequence's cached latents, its
    own new rows among them; position t attends slots <= logical[t].
    The latents are up-projected a block at a time as far as the last
    real query reaches; each block of queries then walks the key blocks
    up to its own diagonal with a running maximum and sum.  `selected`
    (T, S) bool, where given, is a second mask beside the causal one:
    position t attends slot s only where ``selected[t, s]`` too (a
    learned indexer's choice, models/glm_dsa_decode.py).  Returns
    (T, H, v); a pad's row is zeros."""
    T, H, _ = q.shape
    S = ckv.shape[0]
    dt = cfg.dtype
    kb, qb = _block_of(cfg, S), _block_of(cfg, T)
    reach = jnp.where(real, logical, -1)

    def blocks_to(top):                 # key blocks covering [0, top]
        return (top + kb) // kb

    # (the loops' bodies name their scope again: a nested loop is
    # lowered as a function of its own, whose operations would carry
    # the loop's name and not the stack around it)
    def up(j, kv):
        k, v = expand_keys(lax.dynamic_slice_in_dim(ckv, j * kb, kb),
                           lax.dynamic_slice_in_dim(kpe, j * kb, kb),
                           p, cfg)
        return (lax.dynamic_update_slice_in_dim(kv[0], k, j * kb, 0),
                lax.dynamic_update_slice_in_dim(kv[1], v, j * kb, 0))

    keys, values = lax.fori_loop(
        0, blocks_to(jnp.max(reach)), up,
        (jnp.zeros((S, H, cfg.qk_head_dim), dt),
         jnp.zeros((S, H, cfg.v_head_dim), dt)))
    scale = softmax_scale(cfg)

    def queries(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb)
        at = lax.dynamic_slice_in_dim(reach, i * qb, qb)
        pick = None if selected is None \
            else lax.dynamic_slice_in_dim(selected, i * qb, qb)

        @jax.named_scope(scopes.MLA)
        def over(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_slice_in_dim(keys, j * kb, kb)
            vj = lax.dynamic_slice_in_dim(values, j * kb, kb)
            s = jnp.einsum("qhd,khd->hqk", qi, kj).astype(jnp.float32)
            ok = (j * kb + jnp.arange(kb))[None, :] <= at[:, None]
            if pick is not None:
                ok &= lax.dynamic_slice_in_dim(pick, j * kb, kb, axis=1)
            s = jnp.where(ok[None], s * scale, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # a row with nothing to attend yet has m_new == -1e30 and
            # exp(0) == 1 on every masked key: zero them
            e = jnp.where(ok[None], jnp.exp(s - m_new[..., None]), 0.0)
            shrink = jnp.exp(m - m_new)
            return (m_new, l * shrink + jnp.sum(e, axis=-1),
                    acc * shrink[..., None] + jnp.einsum(
                        "hqk,khv->hqv", e.astype(dt), vj
                    ).astype(jnp.float32))

        _, l, acc = lax.fori_loop(
            0, blocks_to(jnp.max(at)), over,
            (jnp.full((H, qb), -1e30, jnp.float32),
             jnp.zeros((H, qb), jnp.float32),
             jnp.zeros((H, qb, cfg.v_head_dim), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(1, 0, 2).astype(dt)

    return lax.map(queries, jnp.arange(T // qb)).reshape(
        T, H, cfg.v_head_dim)


@jax.named_scope(scopes.MLA)
def attend_flash(q, ckv, kpe, p, prefix_len, pad, cfg: KimiK2Config):
    """`attend_blockwise` for a tail of ``T - pad`` real columns behind
    `prefix_len` slots, as one kernel (ops/mla_flash_prefill.py): the
    view's latents are up-projected once, all S of them (two einsums,
    under a millisecond a layer at the published widths), and the
    rotary key stays the one row a slot it is."""
    k_nope, v = expand_latents(ckv, p, cfg)
    return flash.mla_flash_prefill(q, k_nope, kpe, v, prefix_len, pad,
                                   scale=softmax_scale(cfg))


def _takes_kernel(cfg: KimiK2Config, t_pad: int) -> bool:
    """What a paged prefill can see of its input picks its attention:
    on the chip a tail the kernel's tiles divide takes `attend_flash`;
    the CPU and any other tail keep `attend_blockwise`, the parity
    oracle."""
    return jax.default_backend() == "tpu" \
        and flash.fits(t_pad, cfg.max_seq)


def kimi_k2_prefill_attention(cfg: KimiK2Config, t_pad: int,
                              prefix_len: int, n_tail: int
                              ) -> Tuple[bool, int, int]:
    """For the host's count of what `kimi_k2_paged_prefill` ran for a
    `t_pad`-column tail: (whether the kernel attended, the (query tile,
    key tile) pairs it walked, the pairs a walk without the diagonal
    would have: every query tile over the sequence's key tiles).  A
    `jnp` prefill counts no pairs."""
    if not _takes_kernel(cfg, t_pad):
        return False, 0, 0
    walked = flash.walk(t_pad, cfg.max_seq, prefix_len, t_pad - n_tail)
    return True, int(walked.sum()), len(walked) * -(
        -(prefix_len + n_tail) // flash.BLOCK_K)


@jax.named_scope(scopes.MLA)
def attend_paged(q, ckv_pool, rope_lanes, cache, lidx, p, fresh,
                 cfg: KimiK2Config, selected=None):
    """`attend_absorbed` for one decode column of every row of a paged
    cache, over the latent pool where it lies: q (B, 1, H, qk); the
    whole latent pool and ``rotary_lanes`` of the rotary pool; `fresh`
    = this column's (ckv (B, 1, c), kpe (B, 1, r)).  The walk over each
    row's blocks, the running softmax and the weighted sum are one
    kernel (ops/mla_paged_decode.py); ``W_uk`` and ``W_uv`` stay the
    einsums they are.  `selected` (B, max_seq) bool, where given, is
    the slots of its table a row attends and no others (a learned
    indexer's choice, models/glm_dsa_decode.py), its own new position
    at slot ``pos`` among them or not."""
    dt, n = cfg.dtype, cfg.qk_nope_dim
    q_lat = jnp.einsum("bthn,chn->bthc", q[..., :n], p["wk_b"].astype(dt))
    o_lat = mla_paged_decode(
        q_lat[:, 0], q[:, 0, :, n:], ckv_pool, rope_lanes,
        cache["block_tables"], cache["pos"], lidx,
        (fresh[0][:, 0], fresh[1][:, 0]), scale=softmax_scale(cfg),
        start=cache["start"], selected=selected)
    return jnp.einsum("bthc,chv->bthv", o_lat[:, None],
                      p["wv_b"].astype(dt))


def kimi_k2_prefill(params, tokens: jnp.ndarray, cfg: KimiK2Config, *,
                    lengths: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache: tokens
    (B, T0) int32 -> (last_logits (B, padded_vocab) float32, cache).
    Ragged rows are LEFT-padded with `lengths` (B,): a pad's key is
    masked, its row is routed to no expert, and a token's rotary
    position counts from its row's first real column."""
    B, T0 = tokens.shape
    cache = kimi_k2_init_cache(cfg, B)
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

        def attend(q, ckv, kpe):
            new.extend((ckv, kpe))
            return attend_expanded(q, ckv, kpe, p["attn"], mask, cfg)

        x, stats = block(x, p, cfg, positions, attend, valid=real)
        return x, carry, tuple(new), stats

    x, _, (ckv, kpe), stats = walk_layers(cfg, params, x, (), layer)
    with jax.named_scope(scopes.KV_POOL):
        cache["ckv"] = lax.dynamic_update_slice(cache["ckv"], ckv,
                                                (0, 0, 0, 0))
        cache["kpe"] = lax.dynamic_update_slice(cache["kpe"], kpe,
                                                (0, 0, 0, 0))
    cache.update(start=start, pos=jnp.full((B,), T0, jnp.int32))
    return lm_logits(x[:, -1], params, cfg), \
        _with_counters(cache, cfg, stats)


def kimi_k2_paged_prefill(params, cache, tokens: jnp.ndarray,
                          cfg: KimiK2Config, *, row_bt: jnp.ndarray,
                          prefix_len, n_tail, slot
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (gpt2_decode.paged_prefill has the contract): tokens (1, Tt)
    RIGHT-aligned tail of `n_tail` real columns after `prefix_len`
    tokens whose latents are resident in `row_bt`'s blocks.  The new
    latents land in the row's blocks, the pad columns' in the null
    block; the row becomes `slot`'s.  Returns (logits (padded_vocab,)
    of the last real column, cache)."""
    _, Tt = tokens.shape
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad
    logical = prefix_len + col - pad               # position iff real
    # pad columns MUST be masked writes (slot max_seq): their logical
    # index can alias a live prefix slot
    pkv = PagedKV(cache, row_bt[None],
                  jnp.where(real, logical, cfg.max_seq)[None], whole=True)
    positions = jnp.maximum(logical, 0)[None]
    x = embed(params, tokens, cfg)

    def layer(x, pools, p, lidx):
        def attend(q, ckv, kpe):
            nonlocal pools
            pools, (cview, rview) = pkv.attend(lidx, pools, ckv, kpe)
            if _takes_kernel(cfg, Tt):
                return attend_flash(q[0], cview[0], rview[0], p["attn"],
                                    prefix_len, pad, cfg)[None]
            return attend_blockwise(q[0], cview[0], rview[0], p["attn"],
                                    logical, real, cfg)[None]

        x, stats = block(x, p, cfg, positions, attend, valid=real[None])
        return x, pools, (), stats

    x, pools, _, stats = walk_layers(cfg, params, x, pkv.pools, layer)
    # right-aligned: the last column is the last real one.  As eight
    # equal rows: the product of one row is compiled as a float32
    # multiply and sum over the whole head upcast (0.6 GB of it)
    logits = lm_logits(jnp.broadcast_to(x[0, -1], (8, cfg.d_model)),
                       params, cfg)[0]
    out = pkv.commit(pools)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, _with_counters(out, cfg, stats)


def kimi_k2_decode_step(params, cache, tokens, cfg: KimiK2Config
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    A row with ``pos == 0`` holds no sequence (an engine's idle row):
    it is routed to no expert, stays at ``pos == 0``, and what it
    computes is masked garbage as every family's idle rows produce.

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    paged = is_paged(cache)
    # what the program can see of its input picks the path (a paged
    # cache, one column a row, the chip): the kernel walks the pool's
    # blocks where they lie; the CPU gathers the views and keeps the
    # jnp path, the parity oracle
    in_place = paged and jax.default_backend() == "tpu"
    pos, start = cache["pos"], cache["start"]
    rows = jnp.arange(B)
    with jax.named_scope(scopes.MLA):
        # dense: the row's new latent is set into the layer at slot
        # pos; paged: it is attended beside the gathered view, whose
        # slot pos is left out (PagedKV(whole=True) inserts nothing)
        mask = slot_mask(start, pos + (0 if paged else 1),
                         cfg.max_seq)[:, None]                  # (B,1,S)
    pkv = PagedKV(cache, cache["block_tables"], pos[:, None],
                  whole=True) if paged else None
    if in_place:
        with jax.named_scope(scopes.KV_POOL):
            # once for all layers: the pools are read-only in the scan
            rope = rotary_lanes(cache["kpe"])
    x = embed(params, tokens, cfg)[:, None]                     # (B,1,d)

    def layer(x, pools, p, lidx):
        new = []

        def attend(q, ckv, kpe):
            nonlocal pools
            if paged:
                new.extend((ckv, kpe))                   # (B, 1, width)
            if in_place:
                return attend_paged(q, pools[0], rope, cache, lidx,
                                    p["attn"], (ckv, kpe), cfg)
            elif paged:
                pools, views = pkv.attend(lidx, pools, ckv, kpe)
                return attend_absorbed(q, *views, p["attn"], mask, cfg,
                                       fresh=(ckv, kpe))
            else:
                with jax.named_scope(scopes.KV_POOL):
                    views = tuple(
                        held.at[rows, pos].set(row[:, 0])
                        for held, row in zip(
                            dense_layer_kv(cache, lidx), (ckv, kpe)))
                new.extend(views)
                return attend_absorbed(q, *views, p["attn"], mask, cfg)

        x, stats = block(x, p, cfg, (pos - start)[:, None], attend,
                         valid=(pos > 0)[:, None])
        return x, pools, tuple(new), stats

    x, pools, new, stats = walk_layers(
        cfg, params, x, pkv.pools if paged else (), layer)
    logits = lm_logits(x[:, 0], params, cfg)
    out = pkv.commit(pools, *new) if paged \
        else dict(cache, ckv=new[0], kpe=new[1])
    with jax.named_scope(scopes.KV_POOL):
        # a row without a sequence stays one: were its pos to count the
        # steps it idled through, the next step would route it
        out["pos"] = jnp.where(pos > 0, pos + 1, 0)
    return logits, _with_counters(out, cfg, stats)


#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle
kimi_k2_generate = generator(kimi_k2_prefill, kimi_k2_decode_step)
