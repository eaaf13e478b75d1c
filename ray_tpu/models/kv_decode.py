"""The decoder of every family whose cache is plain K/V, written once.

The training path recomputes full-sequence attention; serving needs
incremental decode: O(1) new compute per token against cached
keys/values.  TPU-first choices:

  * static shapes everywhere — the cache is allocated at max_seq and
    slots outside [start, pos] are masked, so ONE compiled step serves
    the whole generation (no shape-polymorphic recompile);
  * prompt ingestion is a SINGLE full-sequence forward (`prefill`) that
    reuses the training-path attention (flash kernel where enabled),
    writes K/V for every prompt position with one dynamic_update_slice
    per cache tensor, and computes logits only at each row's last real
    token — O(1) dispatches instead of O(T0) per-token steps;
  * positions are per-sequence vectors (decode_common cache contract),
    so LEFT-padded ragged prompts decode correctly in one batch and a
    serve slot pool can host rows at different depths;
  * the per-token step is a `lax.scan` over the stacked layer params
    with the cache in the carry (same scan-stacked layout as training —
    one layer traced once).

What a family IS here is its `Block`: how a token is embedded at a
position, a layer's halves around its attention, the attention of a
row's queries against its K/V, the head, and how many heads the cache
keeps.  The programs below own everything else: the cache and both of
its layouts, the positions, the masks, the walk over layers, and the
named scopes a trace is read by.  `gpt2_decode.py` and
`llama_decode.py` are a block each and the programs bound to it under
the family's public names.  (Jamba, Kimi-K2 and Laguna keep a cache
that is more than K/V and have programs of their own; their seam is
the same one, a layer that is handed its attention: laguna.block's
``attend`` argument is `Block.qkv` and `Block.mix` with the call
between them.)

No reference analog (the reference wraps user torch modules); this is
the piece that makes ray_tpu.serve a real LM server.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import (PagedKV, dense_layer_kv,
                                          is_paged, partitioned_cache_init,
                                          slot_mask)

__all__ = ["Block", "init_cache", "init_paged_cache", "prefill",
           "paged_prefill", "decode_step", "verify_step"]


@dataclasses.dataclass(frozen=True)
class Block:
    """What a dense-K/V family supplies.  `lead` is whatever axes a
    program puts before the heads: (B,), (B, T) or (Tt,)."""
    #: ``cfg -> the heads whose K/V a cache keeps`` (every head, or a
    #: grouped-query family's K/V heads: cache bytes scale with these);
    #: raises for a config the family cannot decode
    kv_heads: Callable[[Any], int]
    #: ``(params, tokens (*lead), cfg) -> x (*lead, d)``
    embed: Callable[..., Any]
    #: ``(x, params, pos_ids (*lead) int32, cfg) -> (x, positions)``:
    #: the stream at those LOGICAL positions (learned positions are
    #: added here) and what the layers' `qkv` needs of them (rotary
    #: tables, or None)
    place: Callable[..., Any]
    #: ``(x, p, cfg, positions) -> q (*lead, n_head, hd), k, v (*lead,
    #: kv_heads, hd)``: layer `p`'s first norm and projections, keys as
    #: they are cached (after rotary)
    qkv: Callable[..., Any]
    #: ``(q, k, v, mask, cfg) -> o``: every row's queries against its
    #: OWN K/V (B, S, kv_heads, hd) — q (B, n_head, hd) under mask
    #: (B, S), one query a row, or q (B, T, n_head, hd) under (B, T, S);
    #: o is q's shape up to how the heads are grouped
    attend: Callable[..., Any]
    #: ``(x, o, p, cfg) -> x``: output projection and residual, then
    #: the second norm, the MLP and its residual
    mix: Callable[..., Any]
    #: ``(x, params, cfg) -> x``: the final norm
    norm_f: Callable[..., Any]
    #: ``(x, params, cfg) -> float32 logits`` of normalised `x`
    head: Callable[..., Any]


def _fresh(block: Block, cfg, batch: int, lead, mesh, **tables):
    """A cache of zeros: K/V of (L, *lead, kv_heads, hd), `tables` by
    shape, and the position vectors.  With `mesh` it is born
    partitioned (K/V heads over `tensor` where they divide it; tables /
    pos / start replicated so the host pager stays layout-agnostic):
    each chip allocates only its shard."""
    shape = (cfg.n_layer, *lead, block.kv_heads(cfg), cfg.head_dim)

    def build():
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype),
                **{name: jnp.zeros(s, jnp.int32)
                   for name, s in tables.items()},
                "pos": jnp.zeros((batch,), jnp.int32),
                "start": jnp.zeros((batch,), jnp.int32)}

    return build() if mesh is None else partitioned_cache_init(build, mesh)


def init_cache(block: Block, cfg, batch: int,
               mesh=None) -> Dict[str, jnp.ndarray]:
    """Preallocated (L, B, S, kv_heads, hd) key/value cache +
    per-sequence position vectors (decode_common cache contract)."""
    return _fresh(block, cfg, batch, (batch, cfg.max_seq), mesh)


def init_paged_cache(block: Block, cfg, batch: int, *, num_blocks: int,
                     block_size: int, mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache (decode_common paged contract): K/V pools of
    (L, num_blocks, block_size, kv_heads, hd) shared by all rows,
    per-row block tables initialized to the reserved null block 0 (rows
    hold no storage until the pager assigns blocks)."""
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    return _fresh(block, cfg, batch, (num_blocks, block_size), mesh,
                  block_tables=(batch, cfg.max_seq // block_size))


def _per_query_head(k, v, n_head: int, axis: int):
    """Cached K/V heads repeated to one per query head (nothing, where
    the cache keeps every head)."""
    rep = n_head // k.shape[axis]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=axis), jnp.repeat(v, rep, axis=axis)


def prefill(block: Block, params, tokens: jnp.ndarray, cfg, *,
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion: tokens (B, T0) int32 →
    (last_logits (B, padded_vocab) float32, primed cache).

    Runs ONE full-sequence forward (training-path attention; flash
    kernel under the same dispatch rules) and writes K/V for all T0
    positions with one dynamic_update_slice per cache tensor: the
    cache's heads only, keys as `Block.qkv` gives them, exactly what
    `decode_step` expects.  Ragged batches pass `lengths` (B,): rows
    are LEFT-padded, so row b's real tokens sit at columns
    [T0 - lengths[b], T0), positions count from a row's first real
    column (a pad never shifts a real token's), and the last real token
    is column T0-1 for every row — logits come from that one column,
    never the full (B, T0, V) tensor."""
    from ray_tpu.ops.attention import prefill_attention
    from ray_tpu.parallel.sharding import DECODE_RULES

    B, T0 = tokens.shape
    cache = init_cache(block, cfg, B)
    if lengths is None:
        start = jnp.zeros((B,), jnp.int32)
        pos_ids = jnp.broadcast_to(jnp.arange(T0), (B, T0))
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        # pad columns clip to position 0 — garbage the attention mask
        # keeps unread
        pos_ids = jnp.maximum(jnp.arange(T0)[None, :] - start[:, None], 0)
    with jax.named_scope(scopes.EMBED):
        x = block.embed(params, tokens, cfg)             # (B, T0, d)
        x, positions = block.place(x, params, pos_ids, cfg)
    attn_start = None if lengths is None else start

    def body(x, layer):
        p, = layer
        q, k, v = block.qkv(x, p, cfg, positions)
        with jax.named_scope(scopes.ATTN):
            o = prefill_attention(
                q, *_per_query_head(k, v, cfg.n_head, 2), start=attn_start,
                use_flash=cfg.use_flash, resident=cfg.flash_resident,
                rules=DECODE_RULES)
        return block.mix(x, o, p, cfg), (k, v)

    with jax.named_scope(scopes.LAYER_SCAN):
        x, (ks, vs) = lax.scan(body, x, (params["blocks"],))
    with jax.named_scope(scopes.KV_POOL):
        cache["k"] = lax.dynamic_update_slice(cache["k"], ks,
                                              (0, 0, 0, 0, 0))
        cache["v"] = lax.dynamic_update_slice(cache["v"], vs,
                                              (0, 0, 0, 0, 0))
    cache["pos"] = jnp.full((B,), T0, jnp.int32)
    cache["start"] = start
    x = block.norm_f(x, params, cfg)
    last = x[:, -1]                 # left padding ⇒ last real token
    return block.head(last, params, cfg), cache


@jax.named_scope(scopes.ATTN)
def _attend_tail(q, kview, vview, mask, cfg):
    """ONE row's Tt queries q (Tt, n_head, hd) against its gathered
    view (S, kv_heads, hd) under mask (Tt, S): the hidden states a
    prefill of the whole prompt gives."""
    kview, vview = _per_query_head(kview, vview, cfg.n_head, 1)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    scores = jnp.einsum("qhd,khd->hqk", q,
                        kview).astype(jnp.float32) * scale
    scores = jnp.where(mask[None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("hqk,khd->qhd", probs, vview)


def paged_prefill(block: Block, params, cache, tokens: jnp.ndarray, cfg,
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
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad                          # (Tt,), False on pads
    logical = prefix_len + col - pad           # position iff real
    pos_ids = jnp.maximum(logical, 0)          # pads clip to position 0
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
    with jax.named_scope(scopes.EMBED):
        x = block.embed(params, tokens[0], cfg)          # (Tt, d)
        x, positions = block.place(x, params, pos_ids, cfg)

    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        q, k, v = block.qkv(x, p, cfg, positions)        # (Tt, ., hd)
        pools, (kview, vview) = pkv.attend(lidx, pools, k[None],
                                          v[None])
        o = _attend_tail(q, kview[0], vview[0], mask, cfg)
        return (block.mix(x, o, p, cfg), lidx + 1, pools), \
            (k[None], v[None])

    with jax.named_scope(scopes.LAYER_SCAN):
        (x, _, pools), (new_k, new_v) = lax.scan(
            body, (x, jnp.int32(0), pkv.pools),
            (params["blocks"],))
    x = block.norm_f(x, params, cfg)
    last = x[-1]                    # right-aligned ⇒ last real token
    logits = block.head(last, params, cfg)
    out = pkv.commit(pools, new_k, new_v)
    with jax.named_scope(scopes.KV_POOL):
        out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
        out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
        out["start"] = cache["start"].at[slot].set(0)
    return logits, out


def _walk_cached(block: Block, params, cache, cfg, x, positions, mask,
                 pkv, write):
    """`x` through every layer of a program that attends each row's own
    cache: a paged cache through `pkv` (the pool is carried and updated
    where it lies), a dense one through ``write(layer K or V, new rows)
    -> the layer with the rows in it`` (stacked as the scan's output,
    which IS the new cache).  Returns (logits of every position, the
    cache with the new K/V; its `pos` is the caller's to move)."""
    def body(carry, layer):
        x, lidx, pools = carry
        p, = layer
        q, k, v = block.qkv(x, p, cfg, positions)
        if pkv:
            # PagedKV takes (B, T, kv_heads, hd); a step's rows are T=1
            new = (k, v) if k.ndim == 4 else (k[:, None], v[:, None])
            pools, (ck, cv) = pkv.attend(lidx, pools, *new)
        else:
            lk, lv = dense_layer_kv(cache, lidx)
            with jax.named_scope(scopes.KV_POOL):
                new = ck, cv = write(lk, k), write(lv, v)
        x = block.mix(x, block.attend(q, ck, cv, mask, cfg), p, cfg)
        return (x, lidx + 1, pools), new

    with jax.named_scope(scopes.LAYER_SCAN):
        (x, _, pools), (new_k, new_v) = lax.scan(
            body, (x, jnp.int32(0), pkv.pools if pkv else ()),
            (params["blocks"],))
    logits = block.head(block.norm_f(x, params, cfg), params, cfg)
    if pkv:
        return logits, pkv.commit(pools, new_k, new_v)
    return logits, dict(cache, k=new_k, v=new_v)


def decode_step(block: Block, params, cache, tokens, cfg
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    cache["pos"][b] and LOGICAL position pos - start (positions are
    per-sequence vectors, so rows may sit at different depths — ragged
    prompts, slot-pool serving).

    Works on both cache layouts (the pytree structure is the knob —
    decode_common.is_paged): dense caches index a (B, S, ...) layer and
    write slot pos[b]; paged caches attend over the block-table view
    gathered from the pool with the new token in it — value-identical
    to the dense layer, so everything downstream of the K/V update is
    shared verbatim between layouts — and write the step's K/V into
    the pool where it lies (decode_common.PagedKV: the pool is
    read-only inside the layer scan, the rows land after it).

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    pos = cache["pos"]                                   # (B,)
    start = cache["start"]                               # (B,)
    rows = jnp.arange(tokens.shape[0])
    with jax.named_scope(scopes.EMBED):
        x = block.embed(params, tokens, cfg)             # (B, d)
        x, positions = block.place(x, params, pos - start, cfg)
    # per-slot mask: start[b] <= s <= pos[b] (current token included)
    with jax.named_scope(scopes.ATTN):
        mask = slot_mask(start, pos + 1, cfg.max_seq)    # (B, S)
    pkv = PagedKV(cache, cache["block_tables"],
                  pos[:, None]) if is_paged(cache) else None
    logits, out = _walk_cached(
        block, params, cache, cfg, x, positions, mask, pkv,
        lambda layer, new: layer.at[rows, pos].set(new))  # row b → pos[b]
    with jax.named_scope(scopes.KV_POOL):
        out["pos"] = pos + 1
    return logits, out


def verify_step(block: Block, params, cache, tokens, cfg
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Speculative-decode verify forward: T=k+1 tokens per row in ONE
    dispatch (round 11).  tokens (B, T) int32 is the draft block
    [cur, d_1..d_k] — the last sampled-but-not-yet-ingested token
    followed by the draft's k proposals; row b's t-th token lands at
    cache slot pos[b] + t (at its own logical position), and
    logits[:, t] is the target's distribution for the token AFTER
    tokens[:, t] — exactly what T sequential decode_step dispatches
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
    B, T = tokens.shape
    pos = cache["pos"]                                   # (B,)
    start = cache["start"]                               # (B,)
    rows = jnp.arange(B)
    with jax.named_scope(scopes.KV_POOL):
        offs = jnp.arange(T, dtype=jnp.int32)
        slot_ids = pos[:, None] + offs[None, :]          # (B, T)
    with jax.named_scope(scopes.EMBED):
        pos_ids = jnp.minimum(
            jnp.maximum(slot_ids - start[:, None], 0), cfg.max_seq - 1)
        x = block.embed(params, tokens, cfg)             # (B, T, d)
        x, positions = block.place(x, params, pos_ids, cfg)
    with jax.named_scope(scopes.ATTN):
        # (B, T, S): query t attends slots start[b] <= s <= pos[b] + t
        s = jnp.arange(cfg.max_seq)
        mask = (s[None, None, :] >= start[:, None, None]) & \
               (s[None, None, :] <= slot_ids[:, :, None])
    pkv = write_idx = None
    if is_paged(cache):
        # slots past max_seq are PagedKV's masked writes
        pkv = PagedKV(cache, cache["block_tables"], slot_ids)
    else:
        with jax.named_scope(scopes.KV_POOL):
            # OOB rows dropped by the scatter (mode="drop")
            write_idx = jnp.where(slot_ids < cfg.max_seq, slot_ids,
                                  cfg.max_seq)
    return _walk_cached(
        block, params, cache, cfg, x, positions, mask, pkv,
        lambda layer, new: layer.at[rows[:, None], write_idx].set(
            new, mode="drop"))
