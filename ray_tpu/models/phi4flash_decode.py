"""Autoregressive decoding for the Phi-4-mini-flash family: a cache that
one layer writes and eight read, and a slot that holds both kinds of
per-slot state.

The cache contract of decode_common with everything a cache may hold
but a latent (models/phi4flash.py has the architecture):

  k, v   : (1, B, S, kv_width) dense, (1, blocks, bs, kv_width) paged:
           the FULL layer's K/V, folded (banded_attention.py), the
           model's only positional cache.  The full layer writes it; the full
           layer and every cross layer read it.  A pool of ONE layer:
           a token weighs ``2 * kv_width`` elements whatever the depth.
  conv   : (n_mamba, d_conv - 1, B, d_inner)  the Mamba layers'
  ssm    : (n_mamba, B, d_state, d_inner)     state (jamba_decode.py)
  wk, wv : (n_self, B, window, kv_width)      the window layers' rings
           (banded_attention.py: the row of cache slot s is ``s mod
           window``)

and, in the paged layout, a snapshot pool of all four (``snap_*``; one
entry a slot): the state after a block boundary of some prompt, so
that a later prompt with that prefix resident starts from it.  The
Gated Memory Units keep nothing: the memory they gate is this step's.

Scopes: ``kv_pool`` is the ONE shared pool's (its writes, its gathered
view, the bookkeeping).  The rings are no pool: their writes, slices
(a prefill's, and a decode step's off the chip) and re-lays are the
window layers' own, under ``attn_window``.

  * a decode step advances every ACTIVE row by one token and leaves a
    row with ``pos == 0`` exactly as it is: state, windows and rings.
    The full layer's new row is attended beside the pool (`fresh`) by
    all eight readers and lands once, after the layers
    (`PagedKV.commit`).  Every reader's column is grouped-query
    attention over pair-heads (phi4flash.py): on the chip the walk of
    ops/gqa_paged_decode.py, eight times a step over the same blocks.
    A window layer's column is the same attention over the row's ring:
    on the chip ops/ring_decode.py reads it where it lies in the
    scan's carried stacks (the layer a traced index; no ring is sliced
    out), elsewhere `attend_rows` over the layer's rings sliced out
    (banded_attention.attend_stacked_ring).
  * a prefill runs the self-decoder over every column (they owe the
    state, the rings and the pool their rows) and the CROSS-decoder
    over one column, the prompt's last: for those layers a prefill's
    tail is a decode step.  A chunk that is not its prompt's last runs
    them too, on its own last column: fourteen layers on one token are
    a thousandth of a chunk, and one program serves every chunk.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
# the rings, the banded prefill attention, a row's masked softmax over
# folded K/V and the walk of the paged pool, at this family's pair-head
# geometry
from ray_tpu.models.banded_attention import (attend_banded, attend_paged,
                                             attend_rows,
                                             attend_stacked_ring,
                                             banded_prefill_attention,
                                             prefill_reach, ring_after)
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_SLOT,
                                          STATE_FROM_ZERO, PagedKV,
                                          _refuse_mesh, generator, is_paged,
                                          layer_state, set_layer_state,
                                          slot_mask)
from ray_tpu.models.phi4flash import (Phi4FlashConfig, attn_layer,
                                      attend_masked, causal_mask,
                                      cross_decoder, embed, lm_logits,
                                      mamba_layer, zero_recurrent)

__all__ = ["phi4flash_init_cache", "phi4flash_init_paged_cache",
           "phi4flash_prefill", "phi4flash_paged_prefill",
           "phi4flash_decode_step", "phi4flash_generate",
           "phi4flash_prefill_attention"]

_RINGS = ("wk", "wv")


def phi4flash_prefill_attention(cfg: Phi4FlashConfig, t_pad: int,
                                prefix_len: int, n_tail: int
                                ) -> Tuple[bool, int, int]:
    """`banded_attention.banded_prefill_attention` of
    `phi4flash_paged_prefill`'s window layers and its full layer, in
    the pair-heads' geometry."""
    return banded_prefill_attention(
        cfg.pairs, t_pad, prefix_len, n_tail,
        [(cfg.n_self, cfg.n_head, cfg.window + t_pad, cfg.window),
         (1, cfg.n_head, cfg.max_seq, None)])


def _tensors(cfg: Phi4FlashConfig, batch: int, *lead: int):
    """The full layer's K/V over `lead` and every slot's state."""
    conv, ssm = zero_recurrent(cfg, batch)
    pool = (1, *lead, cfg.kv_width)
    ring = (cfg.n_self, batch, cfg.window, cfg.kv_width)
    # a buffer each: the engine donates the cache, and one buffer under
    # two names would be donated twice
    return {"k": jnp.zeros(pool, cfg.dtype), "v": jnp.zeros(pool, cfg.dtype),
            "conv": conv, "ssm": ssm, "wk": jnp.zeros(ring, cfg.dtype),
            "wv": jnp.zeros(ring, cfg.dtype)}


def _positions(batch: int):
    return {"pos": jnp.zeros((batch,), jnp.int32),
            "start": jnp.zeros((batch,), jnp.int32)}


def phi4flash_init_cache(cfg: Phi4FlashConfig, batch: int,
                         mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (1, B, S, kv_width) K/V of the full layer, the
    recurrent state and the rings of `batch` sequences, positions."""
    _refuse_mesh("phi4flash", mesh)
    return dict(_tensors(cfg, batch, batch, cfg.max_seq),
                **_positions(batch))


def phi4flash_init_paged_cache(cfg: Phi4FlashConfig, batch: int, *,
                               num_blocks: int, block_size: int,
                               mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: the full layer's pool and per-row block tables,
    the rows' state and rings and a snapshot pool of one entry a row
    for all four."""
    _refuse_mesh("phi4flash", mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    tensors = _tensors(cfg, batch, num_blocks, block_size)
    snaps = {"snap_" + n: jnp.zeros_like(tensors[n])
             for n in ("conv", "ssm") + _RINGS}
    return dict(tensors, **snaps,
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                **_positions(batch))


def _last_ring(rows, end, window: int):
    """The ring after slot ``end - 1`` of rows (..., T, width) in slot
    order ending there: the last `window`, a shorter past behind rows
    of zeros that derive to slots below 0."""
    lead = [(0, 0)] * (rows.ndim - 2)
    rows = jnp.pad(rows, lead + [(window, 0), (0, 0)])[..., -window:, :]
    return ring_after(rows, end, window)


# -- the programs -------------------------------------------------------------

def phi4flash_prefill(params, tokens: jnp.ndarray, cfg: Phi4FlashConfig,
                      *, lengths: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache: tokens
    (B, T0) int32 -> (last_logits (B, padded_vocab) float32, cache).
    Ragged rows are LEFT-padded with `lengths` (B,): the attention
    layers mask the pads' keys, the Mamba layers step over the pads.
    The whole score matrix of each self-attention layer: the parity
    oracle, small sizes.  The cross-decoder sees the last column."""
    B, T0 = tokens.shape
    W, pairs = cfg.window, cfg.pairs
    cache = phi4flash_init_cache(cfg, B)
    col = jnp.arange(T0, dtype=jnp.int32)
    if lengths is None:
        start, real = jnp.zeros((B,), jnp.int32), None
        valid = jnp.ones((B, T0), bool)
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        valid = real = col[None, :] >= start[:, None]
    band = causal_mask(T0, W)[None] & valid[:, None, :]
    causal = causal_mask(T0)[None] & valid[:, None, :]
    zero = tuple(a[0] for a in zero_recurrent(cfg, B))
    x = embed(params, tokens, cfg)

    def pair(x, xs):
        p, lam_init = xs
        x, state, _, _ = mamba_layer(x, p["mamba"], cfg, *zero, real=real)
        new = []

        def attend(q, k, v):
            new.extend((k, v))
            with jax.named_scope(scopes.ATTN_WINDOW):
                return attend_masked(q, k, v, band, cfg)

        x = attn_layer(x, p["window"], lam_init, cfg, scopes.ATTN_WINDOW,
                       attend)
        return x, (*state, *new)

    with jax.named_scope(scopes.LAYER_SCAN):
        x, (conv, ssm, ks, vs) = lax.scan(
            pair, x, (params["self"],
                      jnp.asarray(cfg.lambda_init("window"))))
    x, (window, state), _, m = mamba_layer(
        x, params["memory"], cfg, *zero, real=real)
    held = {}

    def full(q, k, v):
        held.update(k=k, v=v)
        with jax.named_scope(scopes.ATTN_FULL):
            return attend_masked(q, k, v, causal, cfg)

    x = attn_layer(x, params["full"], cfg.lambda_init("full")[0], cfg,
                   scopes.ATTN_FULL, full)
    # left-padded: the last column is every row's last token
    x = cross_decoder(
        params, x[:, -1], m[:, -1], cfg,
        lambda q: attend_rows(q, held["k"], held["v"], valid, pairs,
                              pairs.scale))
    with jax.named_scope(scopes.KV_POOL):
        for name in ("k", "v"):
            cache[name] = lax.dynamic_update_slice(
                cache[name], held[name][None], (0, 0, 0, 0))
    with jax.named_scope(scopes.ATTN_WINDOW):
        cache["wk"] = _last_ring(ks, T0, W)
        cache["wv"] = _last_ring(vs, T0, W)
    with jax.named_scope(scopes.SSM_STATE):
        cache["conv"] = jnp.concatenate([conv, window[None]])
        cache["ssm"] = jnp.concatenate([ssm, state[None]])
    cache.update(start=start, pos=jnp.full((B,), T0, jnp.int32))
    return lm_logits(x, params, cfg), cache


def phi4flash_paged_prefill(params, cache, tokens: jnp.ndarray,
                            cfg: Phi4FlashConfig, *, row_bt: jnp.ndarray,
                            prefix_len, n_tail, slot, state=None
                            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (gpt2_decode.paged_prefill has the K/V half of the contract): tokens
    (1, Tt) RIGHT-aligned tail of `n_tail` real columns after
    `prefix_len` tokens whose full-layer K/V are resident in `row_bt`'s
    blocks.

    The per-slot half is jamba_decode.jamba_paged_prefill's for the
    Mamba layers and laguna_decode.laguna_paged_prefill's for the rings,
    told by one `state`, int32 (3,) ``[source, snapshot entry, snapshot
    boundary]``: state and rings start from zeros (``STATE_FROM_ZERO``;
    the rings are read and none of their rows shown), from the slot's
    own rows (``STATE_FROM_SLOT``: the previous chunk of this prompt
    left them) or from snapshot entry ``source >= 0``, which has to be
    the state after exactly `prefix_len` tokens.  They end as the state
    after ``prefix_len + n_tail`` tokens, in row `slot`.  With
    ``snapshot entry >= 0`` the state after ``snapshot boundary``
    tokens (``prefix_len < boundary <= prefix_len + n_tail``) is also
    written into that entry of the snapshot pool.  None is a whole
    prompt from zeros, no snapshot.

    The cross-decoder runs on the last column alone (module docstring),
    over the pool's rows up to it."""
    _, Tt = tokens.shape
    W, pairs = cfg.window, cfg.pairs
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
    # the full layer's keys are the row's gathered view, a window
    # layer's the ring laid before the tail (banded_attention.prefill_reach)
    reach_full = prefill_reach(Tt, prefix_len, n_tail)
    reach_window = prefill_reach(Tt, prefix_len, n_tail, W)
    # the column after which the state is `boundary` tokens old
    capture = jnp.clip(pad + boundary - prefix_len - 1, 0, Tt - 1)
    ring_cut = jnp.clip(boundary - prefix_len + pad, 0, Tt)
    keep = jnp.maximum(entry, 0)
    from_snap = jnp.maximum(source, 0)
    # the slot's rows leave the big state ONCE, before the walk, and go
    # back once after it (jamba_decode.jamba_paged_prefill)
    with jax.named_scope(scopes.SSM_STATE):
        def rows(conv, ssm, row):
            return (lax.dynamic_slice_in_dim(conv, row, 1, axis=2),
                    lax.dynamic_slice_in_dim(ssm, row, 1, axis=1))

        own = rows(cache["conv"], cache["ssm"], slot)
        snapped = rows(cache["snap_conv"], cache["snap_ssm"], from_snap)
        begin = tuple(
            jnp.where(source >= 0, h,
                      jnp.where(source == STATE_FROM_SLOT, o,
                                jnp.zeros_like(o)))
            for o, h in zip(own, snapped))
    with jax.named_scope(scopes.ATTN_WINDOW):
        def ring_of(ring, row):                  # (n_self, window, w)
            return lax.dynamic_index_in_dim(ring, row, 1, keepdims=False)

        # slot order: index j holds slot ``prefix_len - window + j``
        order = (prefix_len + jnp.arange(W)) % W
        rings = tuple(
            jnp.take(jnp.where(source >= 0,
                               ring_of(cache["snap_" + n], from_snap),
                               ring_of(cache[n], slot)), order, axis=1)
            for n in _RINGS)
    x = embed(params, tokens, cfg)                             # (1, Tt, d)

    def pair(x, xs):
        p, lam_init, window, ssm, old = xs
        x, after, snap, _ = mamba_layer(x, p["mamba"], cfg, window, ssm,
                                        real=real[None], capture=capture)
        left = []

        def attend(q, k, v):
            with jax.named_scope(scopes.ATTN_WINDOW):
                laid = tuple(lax.dynamic_update_slice_in_dim(
                    jnp.concatenate([jnp.zeros((W, cfg.kv_width), new.dtype),
                                     new[0]]), ring, pad, axis=0)
                    for ring, new in zip(old, (k, v)))
                # the rings after the tail, and after `boundary` tokens
                left.extend(
                    ring_after(lax.dynamic_slice_in_dim(a, Tt, W),
                             prefix_len + n_tail, W) for a in laid)
                left.extend(
                    ring_after(lax.dynamic_slice_in_dim(a, ring_cut, W),
                             boundary, W) for a in laid)
            return attend_banded(q[0], *laid, *reach_window, pairs,
                                 scopes.ATTN_WINDOW, pairs.scale)[None]

        x = attn_layer(x, p["window"], lam_init, cfg, scopes.ATTN_WINDOW,
                       attend)
        return x, (after, snap, tuple(left))

    n = cfg.n_self
    with jax.named_scope(scopes.LAYER_SCAN):
        x, (ends, snaps, left) = lax.scan(
            pair, x, (params["self"],
                      jnp.asarray(cfg.lambda_init("window")),
                      begin[0][:n], begin[1][:n], rings))
    x, end, snap, m = mamba_layer(
        x, params["memory"], cfg, begin[0][n], begin[1][n],
        real=real[None], capture=capture)
    held = {}

    def full(q, k, v):
        pools, (kview, vview) = pkv.attend(0, pkv.pools, k, v)
        held.update(pools=pools, k=kview, v=vview)
        return attend_banded(q[0], kview[0], vview[0], *reach_full, pairs,
                             scopes.ATTN_FULL, pairs.scale)[None]

    x = attn_layer(x, params["full"], cfg.lambda_init("full")[0], cfg,
                   scopes.ATTN_FULL, full)

    # right-aligned: the last column is the last real one, and the only
    # one the cross-decoder sees, over the pool's rows up to it.  As
    # eight equal rows: the product of one row is compiled as a float32
    # multiply and sum over the whole weight upcast
    # (kimi_k2_decode.kimi_k2_paged_prefill); the walk itself is one
    # row's
    def read_pool(q):
        seen = (jnp.arange(held["k"].shape[1]) <= logical[-1])[None]
        o = attend_rows(q[:1], held["k"], held["v"], seen, pairs,
                        pairs.scale)
        return jnp.broadcast_to(o, (8,) + o.shape[1:])

    x = cross_decoder(params, jnp.broadcast_to(x[0, -1], (8, cfg.d_model)),
                      jnp.broadcast_to(m[0, -1], (8, cfg.d_inner)), cfg,
                      read_pool)
    logits = lm_logits(x, params, cfg)[0]
    out = pkv.commit(held["pools"])
    with jax.named_scope(scopes.SSM_STATE):
        def land(conv, ssm, row, window, state):
            return (lax.dynamic_update_slice_in_dim(conv, window, row, 2),
                    lax.dynamic_update_slice_in_dim(ssm, state, row, 1))

        def stacked(scanned, last):
            return tuple(jnp.concatenate([a, b[None]])
                         for a, b in zip(scanned, last))

        out["conv"], out["ssm"] = land(cache["conv"], cache["ssm"], slot,
                                       *stacked(ends, end))
        # without a snapshot to leave, entry `keep` gets back what it has
        kept = rows(cache["snap_conv"], cache["snap_ssm"], keep)
        out["snap_conv"], out["snap_ssm"] = land(
            cache["snap_conv"], cache["snap_ssm"], keep,
            *(jnp.where(entry >= 0, new, old)
              for new, old in zip(stacked(snaps, snap), kept)))
    with jax.named_scope(scopes.ATTN_WINDOW):
        for at, name in enumerate(_RINGS):
            out[name] = lax.dynamic_update_slice_in_dim(
                cache[name], left[at][:, None], slot, axis=1)
            pool = cache["snap_" + name]
            out["snap_" + name] = lax.dynamic_update_slice_in_dim(
                pool, jnp.where(entry >= 0, left[2 + at],
                                ring_of(pool, keep))[:, None], keep, axis=1)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, out


def phi4flash_decode_step(params, cache, tokens, cfg: Phi4FlashConfig
                          ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    A row with ``pos == 0`` holds no sequence that decodes (module
    docstring): its state, windows and rings are left as they are and
    it stays at ``pos == 0``; what it computes is the masked garbage
    every family's idle rows produce.

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    W, pairs = cfg.window, cfg.pairs
    paged = is_paged(cache)
    pos, start = cache["pos"], cache["start"]
    active = pos > 0
    rows = jnp.arange(B)
    with jax.named_scope(scopes.ATTN_WINDOW):
        # an idle row writes nowhere (row `window` is dropped)
        ring_at = jnp.where(active, pos % W, W)
    x = embed(params, tokens, cfg)                             # (B, d)

    def pair(carry, xs):
        x, conv, ssm, wk, wv = carry
        p, lam_init, j = xs
        x, state, _, _ = mamba_layer(
            x[:, None], p["mamba"], cfg,
            *layer_state(scopes.SSM_STATE, conv, ssm, j),
            real=active[:, None])
        conv, ssm = set_layer_state(scopes.SSM_STATE, conv, ssm, j,
                                    *state)
        rings = [wk, wv]

        def attend(q, k, v):
            with jax.named_scope(scopes.ATTN_WINDOW):
                for at, new in enumerate((k, v)):
                    rings[at] = rings[at].at[j, rows, ring_at].set(
                        new, mode="drop")
            return attend_stacked_ring(q, rings, j, pos, start, pairs,
                                        pairs.scale)

        x = attn_layer(x[:, 0], p["window"], lam_init, cfg,
                       scopes.ATTN_WINDOW, attend)
        return (x, conv, ssm, *rings), None

    n = cfg.n_self
    with jax.named_scope(scopes.LAYER_SCAN):
        (x, conv, ssm, wk, wv), _ = lax.scan(
            pair, (x, cache["conv"], cache["ssm"], cache["wk"],
                   cache["wv"]),
            (params["self"], jnp.asarray(cfg.lambda_init("window")),
             jnp.arange(n, dtype=jnp.int32)))
    # the memory is THIS step's: the Gated Memory Units keep nothing
    x, state, _, m = mamba_layer(
        x[:, None], params["memory"], cfg,
        *layer_state(scopes.SSM_STATE, conv, ssm, n), real=active[:, None])
    conv, ssm = set_layer_state(scopes.SSM_STATE, conv, ssm, n, *state)
    x, m = x[:, 0], m[:, 0]
    # the full layer's new row: attended beside the pool by the full
    # layer and by every cross layer, landed once after them
    fresh = {}

    def read_pool(q):
        if paged:
            # the kernel on the chip, the gathered views off it
            return attend_paged(q, (cache["k"], cache["v"]), 0, cache,
                                (fresh["k"], fresh["v"]), pairs,
                                pairs.scale)
        return attend_rows(q, fresh["k"], fresh["v"], fresh["mask"], pairs,
                           pairs.scale)

    def full(q, k, v):
        if paged:
            fresh.update(k=k, v=v)
        else:
            with jax.named_scope(scopes.KV_POOL):
                fresh.update(k=cache["k"][0].at[rows, pos].set(k),
                             v=cache["v"][0].at[rows, pos].set(v),
                             mask=slot_mask(start, pos + 1, cfg.max_seq))
        with jax.named_scope(scopes.ATTN_FULL):
            return read_pool(q)

    x = attn_layer(x, params["full"], cfg.lambda_init("full")[0], cfg,
                   scopes.ATTN_FULL, full)
    x = cross_decoder(params, x, m, cfg, read_pool)
    logits = lm_logits(x, params, cfg)
    if paged:
        # the pool was read-only in the walk: the row lands now
        out = PagedKV(cache, cache["block_tables"], pos[:, None],
                      whole=True).commit(
            (cache["k"], cache["v"]),
            *(fresh[name][None, :, None] for name in ("k", "v")))
    else:
        out = dict(cache, k=fresh["k"][None], v=fresh["v"][None])
    out.update(conv=conv, ssm=ssm, wk=wk, wv=wv)
    with jax.named_scope(scopes.KV_POOL):
        # a row without a sequence stays one
        out["pos"] = jnp.where(active, pos + 1, 0)
    return logits, out


#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle.  kv_layout="paged" re-lays the full layer's K/V into
#: blocks after the prefill (state and rings are per row in both
#: layouts)
phi4flash_generate = generator(phi4flash_prefill, phi4flash_decode_step)
