"""Autoregressive decoding for the Laguna family: a cache whose layers
differ in reach.

The cache contract of decode_common with two kinds of layer in it
(models/laguna.py).  A FULL layer attends the whole context: its K/V
rows are positional and go through the pool as every family's do, the
pool's leading axis counting the full layers alone.  A WINDOW layer
attends the last ``window`` positions: it keeps, per slot and not per
token, a RING of its last ``window`` K/V rows, and nothing else.  K and
V of one token are folded into one row (``kv_width`` = n_kv_head *
head_dim lanes; a K/V head is a lane slice of it):

  k, v   : (n_full, B, S, kv_width)  dense
           (n_full, blocks, bs, kv_width)  paged
  wk, wv : (n_window, B, window, kv_width)  both layouts: the row of
           cache slot ``s`` is ``s mod window``, keys after rotary

and, in the paged layout, a snapshot pool of the rings (``snap_wk``,
``snap_wv``; one entry a slot): the ring after a block boundary of some
prompt, so that a later prompt with that prefix resident starts from it
(serve/kv_pager.py ``StateSnapshots``, the road models/jamba_decode.py
built for its recurrent state; the paged prefill's `state` argument is
the same).  So a block of the pool reserves rows for the full layers
alone, and a window layer costs ``window`` rows a slot whatever the
context.

What a ring row holds is derived, not stored: at cache position ``p``
(the newest row written) row ``r`` holds slot ``p - ((p - r) mod
window)``, attendable iff that is ``>= start``.  A previous tenant's
rows are never attended: for a sequence shorter than the window the
rows it has not written derive to negative slots.

  * a decode step writes every ACTIVE row's new K/V at ``pos mod
    window`` of each ring and attends the ring; a row with ``pos == 0``
    (empty, retired, or parked between two chunks of its prompt) is
    left exactly as it is.  A full layer of a paged cache on the chip
    is one kernel (ops/gqa_paged_decode.py): each row's own blocks are
    read where they lie, as far as its own context reaches, under a
    running softmax; off the chip the ``jnp`` reference gathers the
    rows' views.  A window layer on the chip is one kernel too, in
    both layouts (ops/ring_decode.py,
    `banded_attention.attend_stacked_ring`): each
    row's ring is read once where it lies in the stacked rings, after
    the row's write; off the chip the layer's rings are sliced out of
    the stack and `attend_rows` takes the whole score row.
  * a prefill attends BANDED (`banded_attention.attend_banded`, for
    every family whose K/V are folded): a tile of queries walks
    the key tiles between its first query's window edge (or 0) and its
    own diagonal, on the chip as one kernel a layer
    (ops/banded_flash.py), off it in ``jnp`` (`banded_walk`).  A window
    layer's keys are the slot's ring (from zeros, from a snapshot or
    from the previous chunk, as `state` says) laid before the tail's
    own.

``cache["experts"]`` holds what the expert layers' routing did in the
LAST program (decode_common.EXPERT_COUNTERS).
"""

from __future__ import annotations

import collections
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.banded_attention import (attend_banded, attend_masked,
                                             attend_paged,
                                             attend_stacked_ring,
                                             banded_prefill_attention,
                                             prefill_reach, ring_after)
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_ZERO,
                                          PagedKV, _positions, _refuse_mesh,
                                          generator, is_paged, slot_mask)
from ray_tpu.models.experts import _with_counters
from ray_tpu.models.laguna import (ATTN_SCOPE, FULL, WINDOW, LagunaConfig,
                                   block, causal_mask, embed, lm_logits,
                                   walk_layers)

__all__ = ["laguna_init_cache", "laguna_init_paged_cache",
           "laguna_prefill", "laguna_paged_prefill", "laguna_decode_step",
           "laguna_generate", "laguna_prefill_attention"]


def _tensors(cfg: LagunaConfig, batch: int, *lead: int):
    """The full layers' K/V over `lead` and every slot's rings."""
    full = (len(cfg.layers_of(FULL)), *lead, cfg.kv_width)
    ring = (len(cfg.layers_of(WINDOW)), batch, cfg.window, cfg.kv_width)
    return {"k": jnp.zeros(full, cfg.dtype), "v": jnp.zeros(full, cfg.dtype),
            "wk": jnp.zeros(ring, cfg.dtype),
            "wv": jnp.zeros(ring, cfg.dtype)}


def laguna_init_cache(cfg: LagunaConfig, batch: int,
                      mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (n_full, B, S, kv_width) K/V, the window layers'
    rings of `batch` sequences, position vectors, expert counters."""
    _refuse_mesh("laguna", mesh)
    return dict(_tensors(cfg, batch, batch, cfg.max_seq),
                **_positions(batch))


def laguna_init_paged_cache(cfg: LagunaConfig, batch: int, *,
                            num_blocks: int, block_size: int,
                            mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: the full layers' K/V pools and per-row block
    tables, the rows' rings and a snapshot pool of one entry a row."""
    _refuse_mesh("laguna", mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    tensors = _tensors(cfg, batch, num_blocks, block_size)
    return dict(tensors, snap_wk=jnp.zeros_like(tensors["wk"]),
                snap_wv=jnp.zeros_like(tensors["wv"]),
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                **_positions(batch))


def laguna_prefill_attention(cfg: LagunaConfig, t_pad: int, prefix_len: int,
                             n_tail: int) -> Tuple[bool, int, int]:
    """`banded_prefill_attention` of `laguna_paged_prefill`'s layers."""
    views = {FULL: (cfg.max_seq, None),
             WINDOW: (cfg.window + t_pad, cfg.window)}
    alike = collections.Counter(
        (H, *views[kind])
        for kind, H in zip(cfg.layer_types, cfg.heads_per_layer))
    return banded_prefill_attention(
        cfg, t_pad, prefix_len, n_tail,
        [(n, *layer) for layer, n in alike.items()])


# -- the programs -------------------------------------------------------------

def laguna_prefill(params, tokens: jnp.ndarray, cfg: LagunaConfig, *,
                   lengths: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache: tokens
    (B, T0) int32 -> (last_logits (B, padded_vocab) float32, cache).
    Ragged rows are LEFT-padded with `lengths` (B,): a pad's key is
    masked, its row is routed to no expert, and a token's rotary
    position counts from its row's first real column.  The whole score
    matrix of each layer: the parity oracle, small sizes."""
    B, T0 = tokens.shape
    cache = laguna_init_cache(cfg, B)
    col = jnp.arange(T0, dtype=jnp.int32)
    if lengths is None:
        start = jnp.zeros((B,), jnp.int32)
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
    real = col[None, :] >= start[:, None]                    # (B, T0)
    positions = jnp.maximum(col[None, :] - start[:, None], 0)
    x = embed(params, tokens, cfg)
    new = {FULL: [], WINDOW: []}

    def layer(x, p, lidx, kind, j):
        def attend(q, k, v):
            new[kind].append((k, v))
            with jax.named_scope(ATTN_SCOPE[kind]):
                mask = causal_mask(T0, kind, cfg)[None] & real[:, None, :]
                return attend_masked(q, k, v, mask, cfg)

        return block(x, p, cfg, kind, positions, attend, valid=real)

    x, stats = walk_layers(cfg, params, x, layer)
    with jax.named_scope(scopes.KV_POOL):
        for name, at in (("k", 0), ("v", 1)):
            if new[FULL]:
                cache[name] = lax.dynamic_update_slice(
                    cache[name], jnp.stack([kv[at] for kv in new[FULL]]),
                    (0, 0, 0, 0))
            if new[WINDOW]:
                # the last `window` columns, a short prompt's behind
                # rows of zeros that derive to slots below 0
                rows = jnp.stack([kv[at] for kv in new[WINDOW]])
                rows = jnp.pad(rows, ((0, 0), (0, 0), (cfg.window, 0),
                                      (0, 0)))[:, :, -cfg.window:]
                cache["w" + name] = ring_after(rows, T0, cfg.window)
    cache.update(start=start, pos=jnp.full((B,), T0, jnp.int32))
    return lm_logits(x[:, -1], params, cfg), \
        _with_counters(cache, cfg, stats)


def laguna_paged_prefill(params, cache, tokens: jnp.ndarray,
                         cfg: LagunaConfig, *, row_bt: jnp.ndarray,
                         prefix_len, n_tail, slot, state=None
                         ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (gpt2_decode.paged_prefill has the K/V half of the contract): tokens
    (1, Tt) RIGHT-aligned tail of `n_tail` real columns after
    `prefix_len` tokens whose full-layer K/V are resident in `row_bt`'s
    blocks.

    The window layers' half is jamba_decode.jamba_paged_prefill's, with
    the rings in the recurrent state's place: `state` is int32 (3,)
    ``[source, snapshot entry, snapshot boundary]``.  The slot's rings
    start from its own rows (``STATE_FROM_SLOT``: the previous chunk of
    this prompt left them; ``STATE_FROM_ZERO`` reads them too and shows
    none of them, there being no slot before the tail) or from snapshot
    entry ``source >= 0``, which has to be the rings after exactly
    `prefix_len` tokens.  They end as the rings after ``prefix_len +
    n_tail`` tokens, in row `slot`.  With ``snapshot entry >= 0`` the
    rings after ``snapshot boundary`` tokens (``prefix_len < boundary <=
    prefix_len + n_tail``) are also written into that entry of the
    snapshot pool.  None is a whole prompt from zeros, no snapshot."""
    _, Tt = tokens.shape
    W = cfg.window
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    if state is None:
        state = jnp.asarray([STATE_FROM_ZERO, NO_SNAPSHOT, 0], jnp.int32)
    source, entry, boundary = state[0], state[1], state[2]
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad
    logical = prefix_len + col - pad               # position iff real
    # pad columns MUST be masked writes (slot max_seq): their logical
    # index can alias a live prefix slot
    pkv = PagedKV(cache, row_bt[None],
                  jnp.where(real, logical, cfg.max_seq)[None], whole=True)
    pools = pkv.pools
    reach_full = prefill_reach(Tt, prefix_len, n_tail)
    reach_window = prefill_reach(Tt, prefix_len, n_tail, W)
    keep = jnp.maximum(entry, 0)
    with jax.named_scope(scopes.KV_POOL):
        def rows_of(ring, row):                  # (n_window, window, w)
            return lax.dynamic_index_in_dim(ring, row, 1, keepdims=False)

        begin = tuple(
            jnp.where(source >= 0,
                      rows_of(cache["snap_" + n], jnp.maximum(source, 0)),
                      rows_of(cache[n], slot)) for n in ("wk", "wv"))
        # slot order: index j holds slot ``prefix_len - window + j``
        order = (prefix_len + jnp.arange(W)) % W
        begin = tuple(jnp.take(b, order, axis=1) for b in begin)
    x = embed(params, tokens, cfg)[0]                          # (Tt, d)
    ends, snaps = [], []

    def layer(x, p, lidx, kind, j):
        def attend(q, k, v):
            nonlocal pools
            if kind == FULL:
                pools, (kview, vview) = pkv.attend(j, pools, k[None],
                                                   v[None])
                return attend_banded(q, kview[0], vview[0], *reach_full,
                                     cfg, scopes.ATTN_FULL)
            with jax.named_scope(scopes.KV_POOL):
                laid = tuple(lax.dynamic_update_slice_in_dim(
                    jnp.concatenate([jnp.zeros((W, cfg.kv_width), new.dtype),
                                     new]), old[j], pad, axis=0)
                    for old, new in zip(begin, (k, v)))
                # the rings after the tail, and after `boundary` tokens
                ends.append(tuple(
                    ring_after(lax.dynamic_slice_in_dim(a, Tt, W),
                             prefix_len + n_tail, W) for a in laid))
                snaps.append(tuple(
                    ring_after(lax.dynamic_slice_in_dim(
                        a, jnp.clip(boundary - prefix_len + pad, 0, Tt), W),
                        boundary, W) for a in laid))
            return attend_banded(q, *laid, *reach_window, cfg,
                                 scopes.ATTN_WINDOW)

        return block(x, p, cfg, kind, jnp.maximum(logical, 0), attend,
                     valid=real)

    x, stats = walk_layers(cfg, params, x, layer)
    # right-aligned: the last column is the last real one.  As eight
    # equal rows: the product of one row is compiled as a float32
    # multiply and sum over the whole head upcast
    # (kimi_k2_decode.kimi_k2_paged_prefill)
    logits = lm_logits(jnp.broadcast_to(x[-1], (8, cfg.d_model)),
                       params, cfg)[0]
    out = pkv.commit(pools)
    with jax.named_scope(scopes.KV_POOL):
        for at, name in enumerate(("wk", "wv")):
            if not ends:
                break
            out[name] = lax.dynamic_update_slice_in_dim(
                cache[name], jnp.stack([e[at] for e in ends])[:, None],
                slot, axis=1)
            # without a snapshot to leave, entry `keep` gets back what
            # it has
            snap = cache["snap_" + name]
            out["snap_" + name] = lax.dynamic_update_slice_in_dim(
                snap, jnp.where(
                    entry >= 0, jnp.stack([s[at] for s in snaps]),
                    rows_of(snap, keep))[:, None], keep, axis=1)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, _with_counters(out, cfg, stats)


def laguna_decode_step(params, cache, tokens, cfg: LagunaConfig
                       ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    A row with ``pos == 0`` holds no sequence that decodes (module
    docstring): it is routed to no expert, its rings are left as they
    are and it stays at ``pos == 0``; what it computes is the masked
    garbage every family's idle rows produce.

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    W = cfg.window
    paged = is_paged(cache)
    pos, start = cache["pos"], cache["start"]
    active = pos > 0
    rows = jnp.arange(B)
    with jax.named_scope(scopes.ATTN_WINDOW):
        # an idle row writes nowhere (row `window` is dropped)
        ring_at = jnp.where(active, pos % W, W)
    if paged:
        pkv = PagedKV(cache, cache["block_tables"], pos[:, None],
                      whole=True)
    else:
        with jax.named_scope(scopes.ATTN_FULL):
            mask = slot_mask(start, pos + 1, cfg.max_seq)[:, None]
    held = {n: cache[n] for n in ("k", "v", "wk", "wv")}
    fresh = []
    x = embed(params, tokens, cfg)                             # (B, d)

    def layer(x, p, lidx, kind, j):
        def attend(q, k, v):
            if kind == WINDOW:
                with jax.named_scope(scopes.KV_POOL):
                    for n, new in (("wk", k), ("wv", v)):
                        held[n] = held[n].at[j, rows, ring_at].set(
                            new, mode="drop")
                return attend_stacked_ring(q, (held["wk"], held["wv"]),
                                            j, pos, start, cfg)
            if paged:
                fresh.append((k, v))
                # the kernel on the chip, the gathered views off it
                with jax.named_scope(scopes.ATTN_FULL):
                    return attend_paged(q, (held["k"], held["v"]), j,
                                        cache, (k, v), cfg)
            with jax.named_scope(scopes.KV_POOL):
                for n, new in (("k", k), ("v", v)):
                    held[n] = held[n].at[j, rows, pos].set(new)
                view = (held["k"][j], held["v"][j])
            with jax.named_scope(scopes.ATTN_FULL):
                return attend_masked(q[:, None], *view, mask, cfg)[:, 0]

        return block(x, p, cfg, kind, pos - start, attend, valid=active)

    x, stats = walk_layers(cfg, params, x, layer)
    logits = lm_logits(x, params, cfg)
    if paged:
        # the pools were read-only in the walk: the rows land now, every
        # full layer at once (PagedKV.commit)
        out = pkv.commit(
            (held["k"], held["v"]),
            *(jnp.stack([kv[at] for kv in fresh])[:, :, None]
              for at in (0, 1))) if fresh else dict(cache)
        out.update(wk=held["wk"], wv=held["wv"])
    else:
        out = dict(cache, **held)
    with jax.named_scope(scopes.KV_POOL):
        # a row without a sequence stays one: were its pos to count the
        # steps it idled through, the next step would route it
        out["pos"] = jnp.where(active, pos + 1, 0)
    return logits, _with_counters(out, cfg, stats)


#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle.  kv_layout="paged" re-lays the full layers' K/V into
#: blocks after the prefill (the rings are per row in both layouts)
laguna_generate = generator(laguna_prefill, laguna_decode_step)
