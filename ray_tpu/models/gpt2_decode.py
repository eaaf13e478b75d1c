"""Autoregressive decoding with a KV cache for the GPT-2 family.

The training path (gpt2.py) recomputes full-sequence attention; serving
needs incremental decode: O(1) new compute per token against cached
keys/values.  TPU-first choices:

  * static shapes everywhere — the cache is allocated at max_seq and
    slots outside [start, pos] are masked, so ONE compiled step serves
    the whole generation (no shape-polymorphic recompile);
  * prompt ingestion is a SINGLE full-sequence forward (`prefill`) that
    reuses the training-path attention (flash kernel where enabled),
    writes K/V for every prompt position with one dynamic_update_slice
    per cache tensor, and computes logits only at each row's last real
    token — O(1) dispatches instead of the old O(T0) per-token scan;
  * positions are per-sequence vectors (decode_common cache contract),
    so LEFT-padded ragged prompts decode correctly in one batch and a
    serve slot pool can host rows at different depths;
  * the per-token step is a `lax.scan` over the stacked layer params
    with the cache in the carry (same scan-stacked layout as training —
    one layer traced once).

No reference analog (the reference wraps user torch modules); this is
the piece that makes ray_tpu.serve a real LM server.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models import decode_common
from ray_tpu.models.decode_common import (PagedKV, dense_layer_kv,
                                          generate_with, is_paged,
                                          scan_prefill, slot_mask)
from ray_tpu.models.gpt2 import GPT2Config, _layernorm

__all__ = ["init_cache", "init_paged_cache", "prefill", "paged_prefill",
           "decode_step", "verify_step", "generate"]


def init_cache(cfg: GPT2Config, batch: int,
               mesh=None) -> Dict[str, jnp.ndarray]:
    """Preallocated (L, B, S, H, hd) key/value cache + per-sequence
    position vectors (decode_common cache contract).  With `mesh`, the
    cache is born partitioned (heads over `tensor`; each chip
    allocates only its shard)."""
    if cfg.n_experts:
        raise NotImplementedError(
            "KV-cache decoding currently supports dense GPT-2 configs "
            "only (n_experts=0); MoE decode needs per-step routing")
    shape = (cfg.n_layer, batch, cfg.max_seq, cfg.n_head, cfg.head_dim)

    def build():
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                "pos": jnp.zeros((batch,), jnp.int32),
                "start": jnp.zeros((batch,), jnp.int32)}

    if mesh is None:
        return build()
    return decode_common.partitioned_cache_init(build, mesh)


def init_paged_cache(cfg: GPT2Config, batch: int, *, num_blocks: int,
                     block_size: int,
                     mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache (decode_common paged contract): K/V pools of
    (L, num_blocks, block_size, H, hd) shared by all rows, per-row
    block tables initialized to the reserved null block 0 (rows hold no
    storage until the pager assigns blocks).  With `mesh`, the pool is
    born partitioned — pool heads split over `tensor`, block tables /
    pos / start replicated so the host pager stays layout-agnostic."""
    if cfg.n_experts:
        raise NotImplementedError(
            "KV-cache decoding currently supports dense GPT-2 configs "
            "only (n_experts=0); MoE decode needs per-step routing")
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    shape = (cfg.n_layer, num_blocks, block_size, cfg.n_head,
             cfg.head_dim)

    def build():
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                "block_tables": jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32),
                "start": jnp.zeros((batch,), jnp.int32)}

    if mesh is None:
        return build()
    return decode_common.partitioned_cache_init(build, mesh)


@jax.named_scope(scopes.MLP)
def _mlp(xm, p, cfg: GPT2Config):
    """The block's MLP on normalised activations of any rank."""
    hmid = jax.nn.gelu(xm @ p["fc_w"].astype(cfg.dtype)
                       + p["fc_b"].astype(cfg.dtype))
    return (hmid @ p["proj_w"].astype(cfg.dtype)
            + p["proj_b"].astype(cfg.dtype))


@jax.named_scope(scopes.LM_HEAD)
def _lm_head(x, params, cfg: GPT2Config):
    """Final layernorm'd activations -> float32 logits (tied head)."""
    return (x @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)


def prefill(params, tokens: jnp.ndarray, cfg: GPT2Config, *,
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion: tokens (B, T0) int32 →
    (last_logits (B, padded_vocab) float32, primed cache).

    Runs ONE full-sequence forward (training-path attention; flash
    kernel under the same dispatch rules) and writes K/V for all T0
    positions with one dynamic_update_slice per cache tensor.  Ragged
    batches pass `lengths` (B,): rows are LEFT-padded, so row b's real
    tokens sit at columns [T0 - lengths[b], T0) and the last real token
    is column T0-1 for every row — logits come from that one column,
    never the full (B, T0, V) tensor."""
    from ray_tpu.ops.attention import prefill_attention
    from ray_tpu.parallel.sharding import DECODE_RULES

    B, T0 = tokens.shape
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    cache = init_cache(cfg, B)
    if lengths is None:
        start = jnp.zeros((B,), jnp.int32)
        pos_ids = jnp.broadcast_to(jnp.arange(T0), (B, T0))
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        # pad columns clip to wpe row 0 — garbage the attention mask
        # keeps unread
        pos_ids = jnp.maximum(jnp.arange(T0)[None, :] - start[:, None], 0)
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]      # (B, T0, d)
        x = x + params["wpe"].astype(cfg.dtype)[pos_ids]
    attn_start = None if lengths is None else start

    def body(x, layer):
        p, = layer
        xa = _layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        with jax.named_scope(scopes.ATTN):
            w = p["attn"]["qkv_w"].astype(cfg.dtype).reshape(
                d, 3 * h * hd)
            qkv = (xa @ w).reshape(B, T0, 3, h, hd) \
                + p["attn"]["qkv_b"].astype(cfg.dtype)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            o = prefill_attention(q, k, v, start=attn_start,
                                  use_flash=cfg.use_flash,
                                  resident=cfg.flash_resident,
                                  rules=DECODE_RULES)
            wo = p["attn"]["o_w"].astype(cfg.dtype).reshape(h * hd, d)
            x = x + (o.reshape(B, T0, h * hd) @ wo
                     + p["attn"]["o_b"].astype(cfg.dtype))
        x = x + _mlp(_layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"]),
                     p["mlp"], cfg)
        return x, (k, v)

    with jax.named_scope(scopes.LAYER_SCAN):
        x, (ks, vs) = lax.scan(body, x, (params["blocks"],))
    with jax.named_scope(scopes.KV_POOL):
        cache["k"] = lax.dynamic_update_slice(cache["k"], ks,
                                              (0, 0, 0, 0, 0))
        cache["v"] = lax.dynamic_update_slice(cache["v"], vs,
                                              (0, 0, 0, 0, 0))
    cache["pos"] = jnp.full((B,), T0, jnp.int32)
    cache["start"] = start
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    last = x[:, -1]                 # left padding ⇒ last real token
    logits = _lm_head(last, params, cfg)
    return logits, cache


def paged_prefill(params, cache, tokens: jnp.ndarray, cfg: GPT2Config,
                  *, row_bt: jnp.ndarray, prefix_len, n_tail, slot
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool:
    the prefix-reuse fast path (and, with prefix_len=0, the cold path).

    tokens (1, Tt) int32 is the prompt tail RIGHT-aligned in its bucket
    (left-padded — same convention as the batched prefill, so the last
    real token is always column Tt-1); `n_tail` of them are real and
    land at logical positions [prefix_len, prefix_len + n_tail).
    row_bt (max_seq // block_size,) int32 is the row's full block
    table: entries < prefix_len//bs name already-resident prefix blocks
    whose K/V are read, not recomputed — that is the entire point.
    Tail K/V are written into the pool where it lies (pad columns
    are masked writes: dropped, or routed to the reserved null block
    0); attention for the Tt queries runs against the row's gathered
    pool view, the tail in it, with a causal-by-logical-position mask
    (decode_common.PagedKV owns both).  prefix_len / n_tail / slot
    are dynamic scalars — one compiled program per (Tt bucket, pool
    shape) serves every request.

    Returns (last-token logits (padded_vocab,) float32, cache with
    pool K/V updated and row `slot`'s table/pos/start set).  Paged
    rows always use start=0 (slot == logical position — the invariant
    that makes blocks shareable across sequences)."""
    _, Tt = tokens.shape
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad                          # (Tt,), False on pads
    logical = prefix_len + col - pad           # position iff real
    pos_ids = jnp.maximum(logical, 0)          # pads clip to wpe row 0
    # write slots for tail K/V: pad columns MUST be masked writes
    # (slot max_seq) — their logical index can alias a live prefix slot
    pkv = PagedKV(cache, row_bt[None],
                  jnp.where(real, logical, cfg.max_seq)[None])
    # key slot s attendable by query column c iff c is real and
    # s <= logical[c] (all-masked pad columns softmax to uniform —
    # finite garbage that never reaches the pool or the logits)
    with jax.named_scope(scopes.ATTN):
        mask = real[:, None] & (
            jnp.arange(cfg.max_seq)[None, :] <= logical[:, None])
    scale = 1.0 / math.sqrt(hd)
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[0]]   # (Tt, d)
        x = x + params["wpe"].astype(cfg.dtype)[pos_ids]

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        xa = _layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        with jax.named_scope(scopes.ATTN):
            w = p["attn"]["qkv_w"].astype(cfg.dtype).reshape(
                d, 3 * h * hd)
            qkv = (xa @ w).reshape(Tt, 3, h, hd) \
                + p["attn"]["qkv_b"].astype(cfg.dtype)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]    # (Tt,h,hd)
        pools, (kview, vview) = pkv.attend(lidx, pools, k[None],
                                          v[None])
        kview, vview = kview[0], vview[0]                # (S,h,hd)
        with jax.named_scope(scopes.ATTN):
            scores = jnp.einsum("qhd,khd->hqk", q,
                                kview).astype(jnp.float32) * scale
            scores = jnp.where(mask[None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            o = jnp.einsum("hqk,khd->qhd", probs, vview)
            wo = p["attn"]["o_w"].astype(cfg.dtype).reshape(h * hd, d)
            x = x + (o.reshape(Tt, h * hd) @ wo
                     + p["attn"]["o_b"].astype(cfg.dtype))
        x = x + _mlp(_layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"]),
                     p["mlp"], cfg)
        return (x, lidx + 1, pools), (k[None], v[None])

    with jax.named_scope(scopes.LAYER_SCAN):
        (x, _, pools), (new_k, new_v) = lax.scan(
            body, (x, jnp.int32(0), pkv.pools),
            (params["blocks"],))
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    last = x[-1]                    # right-aligned ⇒ last real token
    logits = _lm_head(last, params, cfg)
    out = pkv.commit(pools, new_k, new_v)
    with jax.named_scope(scopes.KV_POOL):
        out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
        out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
        out["start"] = cache["start"].at[slot].set(0)
    return logits, out


def decode_step(params, cache, tokens, cfg: GPT2Config
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    cache["pos"][b] (positions are per-sequence vectors, so rows may
    sit at different depths — ragged prompts, slot-pool serving).

    Works on both cache layouts (the pytree structure is the knob —
    decode_common.is_paged): dense caches index a (B, S, ...) layer and
    write slot pos[b]; paged caches attend over the block-table view
    gathered from the pool with the new token in it — value-identical
    to the dense layer, so everything downstream of the K/V update is
    shared verbatim between layouts — and write the step's K/V into
    the pool where it lies (decode_common.PagedKV: the pool is
    read-only inside the layer scan, the rows land after it).

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    paged = is_paged(cache)
    pos = cache["pos"]                                   # (B,)
    start = cache["start"]                               # (B,)
    rows = jnp.arange(B)
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]      # (B, d)
        x = x + params["wpe"].astype(cfg.dtype)[pos - start]

    # per-slot mask: start[b] <= s <= pos[b] (current token included)
    with jax.named_scope(scopes.ATTN):
        attn_mask = slot_mask(start, pos + 1, cfg.max_seq)   # (B, S)
    pkv = PagedKV(cache, cache["block_tables"],
                  pos[:, None]) if paged else None

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        xa = _layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        with jax.named_scope(scopes.ATTN):
            w = p["attn"]["qkv_w"].astype(cfg.dtype).reshape(
                d, 3 * h * hd)
            qkv = (xa @ w).reshape(B, 3, h, hd) \
                + p["attn"]["qkv_b"].astype(cfg.dtype)
            q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # (B,h,hd)
        if paged:
            new = (k_new[:, None], v_new[:, None])       # (B,1,h,hd)
            pools, (ck, cv) = pkv.attend(lidx, pools, *new)
        else:
            lk, lv = dense_layer_kv(cache, lidx)
            with jax.named_scope(scopes.KV_POOL):
                ck = lk.at[rows, pos].set(k_new)   # row b → pos[b]
                cv = lv.at[rows, pos].set(v_new)
            new = (ck, cv)
        with jax.named_scope(scopes.ATTN):
            # attention of the single query against the cache
            scores = jnp.einsum("bhd,bshd->bhs", q,
                                ck).astype(jnp.float32)
            scores = scores / jnp.sqrt(jnp.float32(hd))
            scores = jnp.where(attn_mask[:, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            o = jnp.einsum("bhs,bshd->bhd", probs, cv)   # (B,h,hd)
            wo = p["attn"]["o_w"].astype(cfg.dtype).reshape(h * hd, d)
            x = x + (o.reshape(B, h * hd) @ wo
                     + p["attn"]["o_b"].astype(cfg.dtype))
        x = x + _mlp(_layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"]),
                     p["mlp"], cfg)
        return (x, lidx + 1, pools), new

    with jax.named_scope(scopes.LAYER_SCAN):
        (x, _, pools), (new_k, new_v) = lax.scan(
            body, (x, jnp.int32(0), pkv.pools if pkv else ()),
            (params["blocks"],))
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = _lm_head(x, params, cfg)
    if paged:
        out = pkv.commit(pools, new_k, new_v)
    else:
        out = dict(cache, k=new_k, v=new_v)
    with jax.named_scope(scopes.KV_POOL):
        out["pos"] = pos + 1
    return logits, out


def verify_step(params, cache, block, cfg: GPT2Config
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Speculative-decode verify forward: T=k+1 tokens per row in ONE
    dispatch (round 11).  block (B, T) int32 is [cur, d_1..d_k] — the
    last sampled-but-not-yet-ingested token followed by the draft's k
    proposals; row b's t-th token lands at cache slot pos[b] + t, and
    logits[:, t] is the target's distribution for the token AFTER
    block[:, t] — exactly what T sequential decode_step dispatches
    would produce, which is what makes greedy spec decode bit-exact
    against the non-speculative oracle.

    Shares decode_step's per-slot masking discipline (the PR 2 ragged
    prefill shape: per-row pos/start, causal within the block) and
    both KV layouts.  Writes past max_seq — possible only in a
    request's final rounds, when the accepted prefix can't reach them
    anyway — are routed to the null block (paged) or dropped (dense)
    instead of clamping onto live slots.  pos is NOT advanced: the
    caller (decode_common.make_spec_verify) moves it by the accepted
    count, which IS the rollback."""
    B, T = block.shape
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    paged = is_paged(cache)
    pos = cache["pos"]                                   # (B,)
    start = cache["start"]                               # (B,)
    rows = jnp.arange(B)
    with jax.named_scope(scopes.KV_POOL):
        offs = jnp.arange(T, dtype=jnp.int32)
        slot_ids = pos[:, None] + offs[None, :]          # (B, T)
    with jax.named_scope(scopes.EMBED):
        pos_ids = jnp.minimum(
            jnp.maximum(slot_ids - start[:, None], 0), cfg.max_seq - 1)
        x = params["wte"].astype(cfg.dtype)[block]       # (B, T, d)
        x = x + params["wpe"].astype(cfg.dtype)[pos_ids]
    with jax.named_scope(scopes.ATTN):
        # (B, T, S): query t attends slots start[b] <= s <= pos[b] + t
        s = jnp.arange(cfg.max_seq)
        attn_mask = (s[None, None, :] >= start[:, None, None]) & \
                    (s[None, None, :] <= slot_ids[:, :, None])
    pkv = None
    if paged:
        # slots past max_seq are PagedKV's masked writes
        pkv = PagedKV(cache, cache["block_tables"], slot_ids)
    else:
        with jax.named_scope(scopes.KV_POOL):
            # OOB rows dropped by the scatter (mode="drop")
            write_idx = jnp.where(slot_ids < cfg.max_seq, slot_ids,
                                  cfg.max_seq)

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        xa = _layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        with jax.named_scope(scopes.ATTN):
            w = p["attn"]["qkv_w"].astype(cfg.dtype).reshape(
                d, 3 * h * hd)
            qkv = (xa @ w).reshape(B, T, 3, h, hd) \
                + p["attn"]["qkv_b"].astype(cfg.dtype)
            q, k_new, v_new = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if paged:
            new = (k_new, v_new)
            pools, (ck, cv) = pkv.attend(lidx, pools, *new)
        else:
            lk, lv = dense_layer_kv(cache, lidx)
            with jax.named_scope(scopes.KV_POOL):
                ck = lk.at[rows[:, None], write_idx].set(
                    k_new, mode="drop")
                cv = lv.at[rows[:, None], write_idx].set(
                    v_new, mode="drop")
            new = (ck, cv)
        with jax.named_scope(scopes.ATTN):
            scores = jnp.einsum("bthd,bshd->bhts", q,
                                ck).astype(jnp.float32)
            scores = scores / jnp.sqrt(jnp.float32(hd))
            scores = jnp.where(attn_mask[:, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            o = jnp.einsum("bhts,bshd->bthd", probs, cv)  # (B,T,h,hd)
            wo = p["attn"]["o_w"].astype(cfg.dtype).reshape(h * hd, d)
            x = x + (o.reshape(B, T, h * hd) @ wo
                     + p["attn"]["o_b"].astype(cfg.dtype))
        x = x + _mlp(_layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"]),
                     p["mlp"], cfg)
        return (x, lidx + 1, pools), new

    with jax.named_scope(scopes.LAYER_SCAN):
        (x, _, pools), (new_k, new_v) = lax.scan(
            body, (x, jnp.int32(0), pkv.pools if pkv else ()),
            (params["blocks"],))
    x = _layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    logits = _lm_head(x, params, cfg)
    if paged:
        return logits, pkv.commit(pools, new_k, new_v)
    return logits, dict(cache, k=new_k, v=new_v)


def _scan_prefill(params, tokens, cfg, *, lengths=None):
    """prefill-shaped wrapper over the per-token reference scan."""
    if lengths is not None:
        raise ValueError("prefill_impl='scan' is the equal-length "
                         "reference path; ragged prompts need the "
                         "batched prefill")
    return scan_prefill(init_cache, decode_step, params, tokens, cfg)


def generate(params, prompt: jnp.ndarray, cfg: GPT2Config, *,
             max_new_tokens: int, temperature: float = 1.0,
             top_k: int = 0, top_p: float = 1.0,
             lengths: Optional[jnp.ndarray] = None,
             key: Optional[jax.Array] = None,
             prefill_impl: str = "batched",
             kv_layout: str = "dense",
             kv_block_size: int = 16) -> jnp.ndarray:
    """GPT-2 generation (see decode_common.generate_with).  `lengths`
    marks LEFT-padded ragged prompts; prefill_impl="scan" keeps the
    per-token reference prefill for parity testing; kv_layout="paged"
    decodes through the block-pool layout (dense is its oracle);
    top_k/top_p are jit-static sampling filters."""
    prefill_fn = prefill if prefill_impl == "batched" else _scan_prefill
    return generate_with(prefill_fn, decode_step, params, prompt, cfg,
                         max_new_tokens=max_new_tokens,
                         lengths=lengths, temperature=temperature,
                         top_k=top_k, top_p=top_p,
                         key=key, kv_layout=kv_layout,
                         kv_block_size=kv_block_size)
