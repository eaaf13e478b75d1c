"""Autoregressive decoding for the Olmo-Hybrid family: K/V for the one
full layer in four, a matrix a head for the three linear layers, in one
cache.

models/solar_open2_decode.py's cache at other shapes, under the same
names (decode_common ``_STATE``: its axes are per name, not per shape).
The K/V tensors hold the full layers only (``n_full`` of them), folded
as models/laguna.py folds them, ``kv_width`` = n_kv_head * head_dim
lanes a row: with as many K/V heads as query heads that is the whole
model width, 3.75 times a grouped-query layer's row, and the POOL, not
the state, bounds the batch.  Beside them, per sequence and not per
token:

  conv : (n_linear, d_conv - 1, B, conv_width)   the three convolutions'
         window, q, k and v side by side, compute dtype
  ssm  : (n_linear, B, heads, key dim, value dim)   the delta rule's
         state, float32: 2.21 MB a layer a slot at the published sizes

and, in the paged layout the serve engine uses, a snapshot pool of the
same two shapes (``snap_conv``, ``snap_ssm``; one entry a slot).  All
four are donated with the pool and updated where they lie.

A decode step advances every ACTIVE row by one token and leaves a row
with ``pos == 0`` exactly as it is, window and state; a prefill sets
its slot's state from what its `state` argument names and walks it
through the real columns only (olmo_hybrid.deltanet_mix: a pad moves
nothing).
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_SLOT,
                                          STATE_FROM_ZERO, PagedKV,
                                          _refuse_mesh, generator,
                                          is_paged, slot_mask)
# the banded prefill attention over folded K/V is Laguna's
from ray_tpu.models.laguna_decode import (attend_banded,
                                          banded_prefill_attention,
                                          prefill_reach)
from ray_tpu.models.olmo_hybrid import (FULL, LINEAR, OlmoHybridConfig,
                                        attend_masked, embed, full_block,
                                        linear_block, lm_logits,
                                        walk_layers, zero_recurrent)
# the state of a matrix a head, a layer or a slot of it at a time, is
# Solar-Open2's, scope and all
from ray_tpu.models.solar_open2_decode import (_land_rows, _layer_state,
                                               _layer_window,
                                               _set_layer_window,
                                               _slot_rows, _stacked)
from ray_tpu.ops.gqa_paged_decode import (gqa_paged_decode,
                                          gqa_paged_decode_reference)

__all__ = ["olmo_hybrid_init_cache", "olmo_hybrid_init_paged_cache",
           "olmo_hybrid_prefill", "olmo_hybrid_paged_prefill",
           "olmo_hybrid_decode_step", "olmo_hybrid_generate",
           "olmo_hybrid_prefill_attention"]


def olmo_hybrid_prefill_attention(cfg: OlmoHybridConfig, t_pad: int,
                                  prefix_len: int, n_tail: int
                                  ) -> Tuple[bool, int, int]:
    """`laguna_decode.banded_prefill_attention` of
    `olmo_hybrid_paged_prefill`'s full layers."""
    return banded_prefill_attention(
        cfg, t_pad, prefix_len, n_tail,
        [(len(cfg.layers_of(FULL)), cfg.n_head, cfg.max_seq, None)])


def _cache(cfg: OlmoHybridConfig, batch: int, *lead: int):
    """The full layers' K/V over `lead`, every row's recurrent state
    and the position vectors."""
    shape = (len(cfg.layers_of(FULL)), *lead, cfg.kv_width)
    conv, ssm = zero_recurrent(cfg, batch)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype), "conv": conv, "ssm": ssm,
            "pos": jnp.zeros((batch,), jnp.int32),
            "start": jnp.zeros((batch,), jnp.int32)}


def olmo_hybrid_init_cache(cfg: OlmoHybridConfig, batch: int,
                           mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (n_full, B, S, kv_width) K/V, the recurrent state of
    `batch` sequences, position vectors."""
    _refuse_mesh("olmo_hybrid", mesh)
    return _cache(cfg, batch, batch, cfg.max_seq)


def olmo_hybrid_init_paged_cache(cfg: OlmoHybridConfig, batch: int, *,
                                 num_blocks: int, block_size: int,
                                 mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: K/V pools of the full layers, per-row block
    tables, the rows' recurrent state and a snapshot pool of one entry
    a row."""
    _refuse_mesh("olmo_hybrid", mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    cache = _cache(cfg, batch, num_blocks, block_size)
    return dict(cache, snap_conv=jnp.zeros_like(cache["conv"]),
                snap_ssm=jnp.zeros_like(cache["ssm"]),
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32))


def olmo_hybrid_prefill(params, tokens: jnp.ndarray, cfg: OlmoHybridConfig,
                        *, lengths: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache: tokens
    (B, T0) int32 -> (last_logits (B, padded_vocab) float32, cache).
    Ragged rows are LEFT-padded with `lengths` (B,): the full layers
    mask the pads' keys, the linear layers step over the pads.  The
    whole score matrix of each full layer: the parity oracle, small
    sizes."""
    B, T0 = tokens.shape
    cache = olmo_hybrid_init_cache(cfg, B)
    col = jnp.arange(T0, dtype=jnp.int32)
    if lengths is None:
        start, real = jnp.zeros((B,), jnp.int32), None
        mask = (col[None, :] <= col[:, None])[None]
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        real = col[None, :] >= start[:, None]                   # (B, T0)
        mask = (col[None, :] <= col[:, None])[None] & real[:, None, :]
    x = embed(params, tokens, cfg)
    new_kv, after = [], []

    def layer(x, p, kind, j):
        if kind == FULL:
            def attend(q, k, v):
                new_kv.append((k, v))
                with jax.named_scope(scopes.ATTN_FULL):
                    return attend_masked(q, k, v, mask, cfg)

            return full_block(x, p, cfg, attend)
        x, state, _ = linear_block(
            x, p, cfg, *_layer_state(cache["conv"], cache["ssm"], j), real)
        after.append(state)
        return x

    x = walk_layers(cfg, params, x, layer)
    with jax.named_scope(scopes.KV_POOL):
        for name, at in (("k", 0), ("v", 1)):
            if new_kv:
                cache[name] = lax.dynamic_update_slice(
                    cache[name], jnp.stack([kv[at] for kv in new_kv]),
                    (0, 0, 0, 0))
    if after:
        with jax.named_scope(scopes.LINEAR_STATE):
            cache["conv"], cache["ssm"] = _stacked(after)
    cache.update(start=start, pos=jnp.full((B,), T0, jnp.int32))
    return lm_logits(x[:, -1], params, cfg), cache


def olmo_hybrid_paged_prefill(params, cache, tokens: jnp.ndarray,
                              cfg: OlmoHybridConfig, *,
                              row_bt: jnp.ndarray, prefix_len, n_tail,
                              slot, state=None
                              ) -> Tuple[jnp.ndarray,
                                         Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool:
    solar_open2_decode.solar_open2_paged_prefill's contract, `state`
    (int32 (3,) ``[source, snapshot entry, snapshot boundary]``) and
    all; tokens (1, Tt) the RIGHT-aligned tail of `n_tail` real columns
    after `prefix_len` tokens whose full-layer K/V are resident in
    `row_bt`'s blocks."""
    _, Tt = tokens.shape
    prefix_len = jnp.asarray(prefix_len, jnp.int32)
    n_tail = jnp.asarray(n_tail, jnp.int32)
    slot = jnp.asarray(slot, jnp.int32)
    if state is None:
        state = jnp.asarray([STATE_FROM_ZERO, NO_SNAPSHOT, 0], jnp.int32)
    source, entry, boundary = state[0], state[1], state[2]
    pad = Tt - n_tail
    col = jnp.arange(Tt, dtype=jnp.int32)
    real = col >= pad                          # (Tt,), False on pads
    logical = prefix_len + col - pad           # position iff real
    # pad columns MUST be masked writes (slot max_seq): their logical
    # index can alias a live prefix slot
    pkv = PagedKV(cache, row_bt[None],
                  jnp.where(real, logical, cfg.max_seq)[None], whole=True)
    pools = pkv.pools
    # a full layer's keys are the row's gathered view
    reach = prefill_reach(Tt, prefix_len, n_tail)
    # the column after which the state is `boundary` tokens old
    capture = jnp.clip(pad + boundary - prefix_len - 1, 0, Tt - 1)
    keep = jnp.maximum(entry, 0)
    # the slot's rows leave the big state ONCE, before the walk, and go
    # back once after it (jamba_decode.jamba_paged_prefill)
    own = _slot_rows(cache["conv"], cache["ssm"], slot)
    held = _slot_rows(cache["snap_conv"], cache["snap_ssm"],
                      jnp.maximum(source, 0))
    with jax.named_scope(scopes.LINEAR_STATE):
        begin = tuple(
            jnp.where(source >= 0, h,
                      jnp.where(source == STATE_FROM_SLOT, o,
                                jnp.zeros_like(o)))
            for o, h in zip(own, held))
    x = embed(params, tokens, cfg)                             # (1, Tt, d)
    ends, snaps = [], []

    def layer(x, p, kind, j):
        if kind == FULL:
            def attend(q, k, v):
                nonlocal pools
                pools, (kview, vview) = pkv.attend(j, pools, k, v)
                return attend_banded(q[0], kview[0], vview[0], *reach, cfg,
                                     scopes.ATTN_FULL)[None]

            return full_block(x, p, cfg, attend)
        x, after, snap = linear_block(
            x, p, cfg, *_layer_state(*begin, j), real[None], capture)
        ends.append(after)
        snaps.append(snap)
        return x

    x = walk_layers(cfg, params, x, layer)
    # right-aligned: the last column is the last real one.  As eight
    # equal rows: the product of one row is compiled as a float32
    # multiply and sum over the whole head upcast
    # (kimi_k2_decode.kimi_k2_paged_prefill)
    logits = lm_logits(jnp.broadcast_to(x[0, -1], (8, cfg.d_model)),
                       params, cfg)[0]
    out = pkv.commit(pools)
    if ends:
        out["conv"], out["ssm"] = _land_rows(
            cache["conv"], cache["ssm"], slot, *_stacked(ends))
        # without a snapshot to leave, entry `keep` gets back what it has
        kept = _slot_rows(cache["snap_conv"], cache["snap_ssm"], keep)
        with jax.named_scope(scopes.LINEAR_STATE):
            left = tuple(jnp.where(entry >= 0, new, old)
                         for new, old in zip(_stacked(snaps), kept))
        out["snap_conv"], out["snap_ssm"] = _land_rows(
            cache["snap_conv"], cache["snap_ssm"], keep, *left)
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, out


def olmo_hybrid_decode_step(params, cache, tokens, cfg: OlmoHybridConfig
                            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    A row with ``pos == 0`` holds no sequence that decodes (module
    docstring): its recurrent state is left as it is and it stays at
    ``pos == 0``; what it computes is the masked garbage every family's
    idle rows produce.

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    paged = is_paged(cache)
    # what the program can see of its input picks the path (a paged
    # cache, the chip): the kernel walks the pool's blocks where they
    # lie; the CPU gathers the views and keeps the jnp path, the parity
    # oracle (laguna_decode.laguna_decode_step)
    walk = gqa_paged_decode if jax.default_backend() == "tpu" \
        else gqa_paged_decode_reference
    pos, start = cache["pos"], cache["start"]
    active = pos > 0
    rows = jnp.arange(B)
    if paged:
        pkv = PagedKV(cache, cache["block_tables"], pos[:, None],
                      whole=True)
    else:
        with jax.named_scope(scopes.ATTN_FULL):
            mask = slot_mask(start, pos + 1, cfg.max_seq)[:, None]
    held = {n: cache[n] for n in ("k", "v", "conv", "ssm")}
    fresh = []
    x = embed(params, tokens, cfg)[:, None]                    # (B, 1, d)

    def layer(x, p, kind, j):
        if kind == LINEAR:
            # the matrices go in and come back as the whole stack: layer
            # j's are updated where they lie (ops/kda.py kda_decode)
            x, (window, held["ssm"]), _ = linear_block(
                x, p, cfg, _layer_window(held["conv"], j), held["ssm"],
                active[:, None], layer=j)
            held["conv"] = _set_layer_window(held["conv"], j, window)
            return x

        def attend(q, k, v):
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            if paged:
                fresh.append((k, v))
                with jax.named_scope(scopes.ATTN_FULL):
                    return walk(
                        q, held["k"], held["v"], cache["block_tables"],
                        pos, j, (k, v), n_kv_head=cfg.n_kv_head,
                        scale=1.0 / math.sqrt(cfg.head_dim),
                        start=start)[:, None]
            with jax.named_scope(scopes.KV_POOL):
                for n, new in (("k", k), ("v", v)):
                    held[n] = held[n].at[j, rows, pos].set(new)
                view = (held["k"][j], held["v"][j])
            with jax.named_scope(scopes.ATTN_FULL):
                return attend_masked(q[:, None], *view, mask, cfg)

        return full_block(x, p, cfg, attend)

    x = walk_layers(cfg, params, x, layer)
    logits = lm_logits(x[:, 0], params, cfg)
    if paged:
        # the pools were read-only in the walk: the rows land now, every
        # full layer at once (PagedKV.commit)
        out = pkv.commit(
            (held["k"], held["v"]),
            *(jnp.stack([kv[at] for kv in fresh])[:, :, None]
              for at in (0, 1))) if fresh else dict(cache)
    else:
        out = dict(cache, k=held["k"], v=held["v"])
    out.update(conv=held["conv"], ssm=held["ssm"])
    with jax.named_scope(scopes.KV_POOL):
        # a row without a sequence stays one: were its pos to count the
        # steps it idled through, the next wave would advance its state
        out["pos"] = jnp.where(active, pos + 1, 0)
    return logits, out


#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle.  kv_layout="paged" re-lays the full layers' K/V into
#: blocks after the prefill (the recurrent state is per row in both
#: layouts)
olmo_hybrid_generate = generator(olmo_hybrid_prefill,
                                 olmo_hybrid_decode_step)
