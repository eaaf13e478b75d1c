"""Autoregressive decoding for the Jamba family: K/V for the attention
layers, recurrent state for the Mamba layers, in one cache.

The cache contract of decode_common, with a second kind of state.  The
K/V tensors hold the ATTENTION layers only (``n_attn`` of them, not
``n_layer``) and go through `PagedKV` as they do for every family.
Beside them, per sequence and not per token:

  conv  : (n_mamba, d_conv - 1, B, d_inner)  the convolution's window,
          compute dtype
  ssm   : (n_mamba, B, d_state, d_inner)     the SSM state, float32

and, in the paged layout the serve engine uses, a snapshot pool of the
same two shapes (``snap_conv``, ``snap_ssm``; one entry a slot): the
state after a block boundary of some prompt, so that a later prompt
with that prefix resident starts from it (serve/kv_pager.py
``StateSnapshots`` keeps the keys).  All four are donated with the pool
and updated where they lie: the walk over layers carries them and
writes one layer's rows back, it never stacks them as a scan's output.

What "a row's past" means for a recurrent layer:

  * a decode step advances every ACTIVE row by one token; a row with
    ``pos == 0`` (empty, retired, or parked between two chunks of its
    prompt: the engine's ``clear_row`` leaves it so) is left exactly as
    it is, window and state.  An active row has ``pos >= 1``: its
    prompt.
  * a prefill sets its slot's state from what its `state` argument
    names (zeros, a snapshot entry, or the slot's own state after the
    previous chunk), never from the previous tenant's, and walks it
    through the real columns only (mamba.mamba_mix: a pad moves
    nothing).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_ZERO,
                                          PagedKV, _refuse_mesh, begin_rows,
                                          dense_layer_kv, generator,
                                          is_paged, layer_state, leave_rows,
                                          set_layer_state, slot_mask)
from ray_tpu.models.jamba import (JambaConfig, attn_out, embed, layer_at,
                                  lm_logits, mlp_residual, qkv,
                                  rmsnorm, walk_layers, zero_recurrent)
from ray_tpu.models.mamba import mamba_mix

__all__ = ["jamba_init_cache", "jamba_init_paged_cache", "jamba_prefill",
           "jamba_paged_prefill", "jamba_decode_step", "jamba_generate"]


def _kv_tensors(cfg: JambaConfig, *lead: int):
    shape = (cfg.n_attn, *lead, cfg.n_kv_head, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype)}


def jamba_init_cache(cfg: JambaConfig, batch: int,
                     mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (n_attn, B, S, n_kv_head, hd) K/V, the recurrent
    state of `batch` sequences, position vectors."""
    _refuse_mesh("jamba", mesh)
    conv, ssm = zero_recurrent(cfg, batch)
    return dict(_kv_tensors(cfg, batch, cfg.max_seq), conv=conv, ssm=ssm,
                pos=jnp.zeros((batch,), jnp.int32),
                start=jnp.zeros((batch,), jnp.int32))


def jamba_init_paged_cache(cfg: JambaConfig, batch: int, *,
                           num_blocks: int, block_size: int,
                           mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: K/V pools of the attention layers, per-row
    block tables, the rows' recurrent state and a snapshot pool of one
    entry a row."""
    _refuse_mesh("jamba", mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    conv, ssm = zero_recurrent(cfg, batch)
    snap_conv, snap_ssm = zero_recurrent(cfg, batch)
    return dict(_kv_tensors(cfg, num_blocks, block_size), conv=conv,
                ssm=ssm, snap_conv=snap_conv, snap_ssm=snap_ssm,
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32),
                pos=jnp.zeros((batch,), jnp.int32),
                start=jnp.zeros((batch,), jnp.int32))


# -- the recurrent state, one layer of it at a time --------------------------

#: every row's (window, state) of a Mamba layer, read and written
#: (decode_common.py has the cache's axes), under this family's scope
_layer_state = functools.partial(layer_state, scopes.SSM_STATE)
_set_layer_state = functools.partial(set_layer_state, scopes.SSM_STATE)


# -- attention over a cache view --------------------------------------------

@jax.named_scope(scopes.ATTN)
def _attend(q, ck, cv, mask, cfg: JambaConfig):
    """q (B, T, h, hd) against cache views ck, cv (B, S, kv, hd) under
    mask (B, T, S): grouped queries, no head repeated."""
    B, T = q.shape[:2]
    kv, hd = cfg.n_kv_head, cfg.head_dim
    qg = q.reshape(B, T, kv, cfg.n_head // kv, hd)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, ck).astype(jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    o = jnp.einsum("bkgts,bskd->btkgd", probs, cv)
    return o.reshape(B, T, cfg.n_head, hd)


def jamba_prefill(params, tokens: jnp.ndarray, cfg: JambaConfig, *,
                  lengths: Optional[jnp.ndarray] = None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache: tokens
    (B, T0) int32 -> (last_logits (B, padded_vocab) float32, cache).
    Ragged rows are LEFT-padded with `lengths` (B,): the attention
    layers mask the pads' keys, the Mamba layers step over the pads."""
    from ray_tpu.ops.attention import prefill_attention
    from ray_tpu.parallel.sharding import DECODE_RULES

    B, T0 = tokens.shape
    cache = jamba_init_cache(cfg, B)
    if lengths is None:
        start, real = jnp.zeros((B,), jnp.int32), None
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        real = jnp.arange(T0)[None, :] >= start[:, None]
    x = embed(params, tokens, cfg)
    rep = cfg.n_head // cfg.n_kv_head

    def mamba_layer(x, rec, m):
        p = layer_at(params["mamba"], m)
        out, (window, state), _, _ = mamba_mix(
            p["mixer"], rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps), cfg,
            *_layer_state(*rec, m), real=real)
        return (mlp_residual(x + out, p, cfg),
                _set_layer_state(*rec, m, window, state))

    def attn_layer(x, rec, a):
        p = layer_at(params["attn"], a)
        q, k, v = qkv(rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps),
                      p["attn"], cfg)
        with jax.named_scope(scopes.ATTN):
            o = prefill_attention(
                q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                start=None if lengths is None else start,
                use_flash=cfg.use_flash, rules=DECODE_RULES)
        x = x + attn_out(o, p["attn"], cfg).astype(x.dtype)
        return mlp_residual(x, p, cfg), rec, (k, v)

    x, (conv, ssm), (ks, vs) = walk_layers(
        cfg, x, (cache["conv"], cache["ssm"]), mamba_layer, attn_layer)
    with jax.named_scope(scopes.KV_POOL):
        cache["k"] = lax.dynamic_update_slice(cache["k"], ks,
                                              (0, 0, 0, 0, 0))
        cache["v"] = lax.dynamic_update_slice(cache["v"], vs,
                                              (0, 0, 0, 0, 0))
    cache.update(conv=conv, ssm=ssm, start=start,
                 pos=jnp.full((B,), T0, jnp.int32))
    return lm_logits(x[:, -1], params, cfg), cache


def jamba_paged_prefill(params, cache, tokens: jnp.ndarray,
                        cfg: JambaConfig, *, row_bt: jnp.ndarray,
                        prefix_len, n_tail, slot, state=None
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (gpt2_decode.paged_prefill has the K/V half of the contract): tokens
    (1, Tt) RIGHT-aligned tail of `n_tail` real columns after
    `prefix_len` tokens whose K/V are resident.

    The recurrent half: `state` is int32 (3,) ``[source, snapshot
    entry, snapshot boundary]``.  The slot's state starts from zeros
    (``STATE_FROM_ZERO``), from its own rows (``STATE_FROM_SLOT``: the
    previous chunk of this prompt left them) or from snapshot entry
    ``source >= 0``, which has to be the state after exactly
    `prefix_len` tokens.  It ends as the state after ``prefix_len +
    n_tail`` tokens, in row `slot`.  With ``snapshot entry >= 0`` the
    state after ``snapshot boundary`` tokens (``prefix_len < boundary <=
    prefix_len + n_tail``) is also written into that entry of the
    snapshot pool.  None is a whole prompt from zeros, no snapshot."""
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
                  jnp.where(real, logical, cfg.max_seq)[None])
    mask = real[:, None] & (
        jnp.arange(cfg.max_seq)[None, :] <= logical[:, None])
    # the column after which the state is `boundary` tokens old
    capture = jnp.clip(pad + boundary - prefix_len - 1, 0, Tt - 1)
    keep = jnp.maximum(entry, 0)
    x = embed(params, tokens, cfg)

    # the slot's rows leave the big state once, before the walk, and go
    # back once after it (decode_common.begin_rows has why)
    begin = begin_rows(scopes.SSM_STATE, cache, slot, source)

    def mamba_layer(x, carry, m):
        pools, rec, snaps = carry
        p = layer_at(params["mamba"], m)
        out, (window, st), snap, _ = mamba_mix(
            p["mixer"], rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps), cfg,
            *_layer_state(*rec, m), real=real[None], capture=capture)
        return mlp_residual(x + out, p, cfg), (
            pools, _set_layer_state(*rec, m, window, st),
            _set_layer_state(*snaps, m, *snap))

    def attn_layer(x, carry, a):
        pools, rec, snaps = carry
        p = layer_at(params["attn"], a)
        q, k, v = qkv(rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps),
                      p["attn"], cfg)
        pools, (kview, vview) = pkv.attend(a, pools, k, v)
        o = _attend(q, kview, vview, mask[None], cfg)
        x = x + attn_out(o, p["attn"], cfg).astype(x.dtype)
        return mlp_residual(x, p, cfg), (pools, rec, snaps), (k, v)

    x, (pools, rec, snaps), (new_k, new_v) = walk_layers(
        cfg, x, (pkv.pools, begin, begin), mamba_layer, attn_layer)
    logits = lm_logits(x[0, -1], params, cfg)   # right-aligned: last real
    out = pkv.commit(pools, new_k, new_v)
    out.update(leave_rows(scopes.SSM_STATE, cache, slot, rec, entry, keep,
                          snaps))
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, out


def jamba_decode_step(params, cache, tokens, cfg: JambaConfig
                      ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    Rows with ``pos == 0`` hold no sequence that decodes (module
    docstring): their recurrent state is left as it is; their K/V write
    is the masked garbage every family's idle rows produce.

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    paged = is_paged(cache)
    pos, start = cache["pos"], cache["start"]            # (B,)
    rows = jnp.arange(B)
    active = (pos > 0)[:, None]                          # (B, 1)
    x = embed(params, tokens, cfg)
    with jax.named_scope(scopes.ATTN):
        attn_mask = slot_mask(start, pos + 1, cfg.max_seq)[:, None]
    pkv = PagedKV(cache, cache["block_tables"],
                  pos[:, None]) if paged else None

    def mamba_layer(x, carry, m):
        pools, rec = carry
        p = layer_at(params["mamba"], m)
        u = rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
        out, (window, state), _, _ = mamba_mix(
            p["mixer"], u[:, None], cfg, *_layer_state(*rec, m),
            real=active)
        rec = _set_layer_state(*rec, m, window, state)
        return mlp_residual(x + out[:, 0], p, cfg), (pools, rec)

    def attn_layer(x, carry, a):
        pools, rec = carry
        p = layer_at(params["attn"], a)
        q, k_new, v_new = qkv(rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps),
                              p["attn"], cfg)
        if paged:
            new = (k_new[:, None], v_new[:, None])       # (B,1,kv,hd)
            pools, (ck, cv) = pkv.attend(a, pools, *new)
        else:
            lk, lv = dense_layer_kv(cache, a)
            with jax.named_scope(scopes.KV_POOL):
                ck = lk.at[rows, pos].set(k_new)   # row b -> slot pos[b]
                cv = lv.at[rows, pos].set(v_new)
            new = (ck, cv)
        o = _attend(q[:, None], ck, cv, attn_mask, cfg)[:, 0]
        x = x + attn_out(o, p["attn"], cfg).astype(x.dtype)
        return mlp_residual(x, p, cfg), (pools, rec), new

    x, (pools, rec), (new_k, new_v) = walk_layers(
        cfg, x, (pkv.pools if pkv else (), (cache["conv"], cache["ssm"])),
        mamba_layer, attn_layer)
    logits = lm_logits(x, params, cfg)
    if paged:
        out = pkv.commit(pools, new_k, new_v)
    else:
        out = dict(cache, k=new_k, v=new_v)
    out.update(conv=rec[0], ssm=rec[1])
    with jax.named_scope(scopes.KV_POOL):
        out["pos"] = pos + 1
    return logits, out


#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned.  kv_layout="paged"
#: re-lays the K/V into blocks after the prefill (the recurrent state is
#: per row in both layouts); dense is the paged path's parity oracle
jamba_generate = generator(jamba_prefill, jamba_decode_step)
