"""The decoder of every family that keeps a MATRIX a head beside K/V,
written once: a delta rule's state for most layers, grouped-query
attention's K/V for the few between them, in one cache.

The cache contract of decode_common with the second kind of state, as
models/jamba_decode.py keeps it, at another size.  The K/V tensors hold
the ATTENTION layers only (``len(cfg.layers_of(block.attn))`` of them),
folded as models/banded_attention.py reads them (``kv_width`` =
n_kv_head * head_dim lanes a row), and go through the pool as every
family's do.  Beside them, per sequence and not per token, under the
names the recurrent state already has (decode_common ``_STATE``: its
axes are per name, not per shape), as `Block.zero_recurrent` shapes
them:

  conv : (rule layers, d_conv - 1, B, width)   the convolutions' window,
         q, k and v side by side, compute dtype
  ssm  : (rule layers, B, heads, key dim, value dim)   the delta rule's
         state, float32: megabytes a layer a slot at published sizes

and, in the paged layout the serve engine uses, a snapshot pool of the
same two shapes (``snap_conv``, ``snap_ssm``; one entry a slot): the
state after a block boundary of some prompt, so that a later prompt
with that prefix resident starts from it (serve/kv_pager.py
``StateSnapshots``).  All four are donated with the pool and updated
where they lie.

What "a row's past" means for a rule layer is what it means for a Mamba
layer (jamba_decode.py): a decode step advances every ACTIVE row by one
token and leaves a row with ``pos == 0`` exactly as it is, window and
state; a prefill sets its slot's state from what its `state` argument
names and walks it through the real columns only (the family's mixer: a
pad moves nothing).

A family with expert layers (its config has ``experts``) keeps in
``cache["experts"]`` what their routing did in the LAST program
(decode_common.EXPERT_COUNTERS); one without keeps no such entry.

What a family IS here is its `Block`; models/solar_open2_decode.py and
models/olmo_hybrid_decode.py are a block each and the programs below
bound to it under the family's public names, as kv_decode.py's
families are.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.banded_attention import (attend_banded, attend_masked,
                                             attend_paged,
                                             banded_prefill_attention,
                                             prefill_reach)
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_ZERO,
                                          PagedKV, _positions, _refuse_mesh,
                                          begin_rows, is_paged, layer_state,
                                          layer_window, leave_rows,
                                          set_layer_window, slot_mask,
                                          stacked)
from ray_tpu.models.experts import _with_counters

__all__ = ["Block", "init_cache", "init_paged_cache", "prefill",
           "paged_prefill", "decode_step", "prefill_attention"]

_SCOPE = scopes.LINEAR_STATE


@dataclasses.dataclass(frozen=True)
class Block:
    """What a matrix-state family supplies.  A layer is of kind `attn`
    or it is a rule layer; `stats` is an expert layer's
    (experts.STATS), or None where the family has none."""
    #: the family's name in `families.FAMILIES`
    family: str
    #: the entry of ``cfg.layer_types`` whose layers keep K/V
    attn: str
    #: ``(cfg, batch) -> (conv, ssm)`` of zeros, stacked over the rule
    #: layers: sequences that have seen nothing
    zero_recurrent: Callable[..., Any]
    #: ``(params, tokens (*lead), cfg) -> x (*lead, d)``
    embed: Callable[..., Any]
    #: ``(x, p, cfg, attend, valid) -> (x, stats)``: an attention layer
    #: around ``attend(q, k, v) -> o``, the caller's (it owns the cache
    #: and sees this layer's new rows, folded); `valid` marks the rows
    #: that hold a token
    attn_block: Callable[..., Any]
    #: ``(x (B, T, d), p, cfg, window, state, real, capture=None,
    #: layer=None) -> (x, stats, (window, state), snap)``: a rule layer
    #: from (`window`, `state`), a layer's or, with `layer` (one column
    #: only), the layers' stack of states updated where it lies; `snap`
    #: = the pair after column `capture`, or None
    rule_block: Callable[..., Any]
    #: ``(cfg, params, x, layer) -> (x, the layers' stats)`` with
    #: ``layer(x, p, kind, j) -> (x, stats)``, `j` counting the kind
    walk_layers: Callable[..., Any]
    #: ``(x, params, cfg) -> float32 logits`` through the final norm
    lm_logits: Callable[..., Any]


def prefill_attention(block: Block, cfg, t_pad: int, prefix_len: int,
                      n_tail: int) -> Tuple[bool, int, int]:
    """`banded_attention.banded_prefill_attention` of `paged_prefill`'s
    attention layers."""
    return banded_prefill_attention(
        cfg, t_pad, prefix_len, n_tail,
        [(len(cfg.layers_of(block.attn)), cfg.n_head, cfg.max_seq, None)])


def _fresh(block: Block, cfg, batch: int, *lead: int):
    """The rows' recurrent state, the attention layers' K/V over `lead`
    and what a cache holds beside its tensors, all zeros."""
    conv, ssm = block.zero_recurrent(cfg, batch)
    shape = (len(cfg.layers_of(block.attn)), *lead, cfg.kv_width)
    return dict(k=jnp.zeros(shape, cfg.dtype), v=jnp.zeros(shape, cfg.dtype),
                conv=conv, ssm=ssm,
                **_positions(batch, experts=hasattr(cfg, "experts")))


def _counted(cache, cfg, stats):
    """`cache` with the program's expert counters, for a family whose
    config has expert layers."""
    return _with_counters(cache, cfg, stats) if hasattr(cfg, "experts") \
        else cache


def init_cache(block: Block, cfg, batch: int,
               mesh=None) -> Dict[str, jnp.ndarray]:
    """Dense cache: (attention layers, B, S, kv_width) K/V, the
    recurrent state of `batch` sequences, position vectors (and expert
    counters)."""
    _refuse_mesh(block.family, mesh)
    return _fresh(block, cfg, batch, batch, cfg.max_seq)


def init_paged_cache(block: Block, cfg, batch: int, *, num_blocks: int,
                     block_size: int, mesh=None) -> Dict[str, jnp.ndarray]:
    """Block-pool cache: K/V pools of the attention layers, per-row
    block tables, the rows' recurrent state and a snapshot pool of one
    entry a row."""
    _refuse_mesh(block.family, mesh)
    if cfg.max_seq % block_size:
        raise ValueError(f"max_seq={cfg.max_seq} must be a multiple of "
                         f"block_size={block_size}")
    cache = _fresh(block, cfg, batch, num_blocks, block_size)
    return dict(cache, snap_conv=jnp.zeros_like(cache["conv"]),
                snap_ssm=jnp.zeros_like(cache["ssm"]),
                block_tables=jnp.zeros(
                    (batch, cfg.max_seq // block_size), jnp.int32))


def prefill(block: Block, params, tokens: jnp.ndarray, cfg, *,
            lengths: Optional[jnp.ndarray] = None
            ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Single-dispatch prompt ingestion into a fresh DENSE cache: tokens
    (B, T0) int32 -> (last_logits (B, padded_vocab) float32, cache).
    Ragged rows are LEFT-padded with `lengths` (B,): the attention
    layers mask the pads' keys, the rule layers step over the pads, and
    a pad is routed to no expert.  The whole score matrix of each
    attention layer: the parity oracle, small sizes."""
    B, T0 = tokens.shape
    cache = init_cache(block, cfg, B)
    col = jnp.arange(T0, dtype=jnp.int32)
    if lengths is None:
        start, real = jnp.zeros((B,), jnp.int32), None
        mask = (col[None, :] <= col[:, None])[None]
    else:
        start = (T0 - jnp.asarray(lengths, jnp.int32)).astype(jnp.int32)
        real = col[None, :] >= start[:, None]                   # (B, T0)
        mask = (col[None, :] <= col[:, None])[None] & real[:, None, :]
    x = block.embed(params, tokens, cfg)
    new_kv, after = [], []

    def layer(x, p, kind, j):
        if kind == block.attn:
            def attend(q, k, v):
                new_kv.append((k, v))
                with jax.named_scope(scopes.ATTN_FULL):
                    return attend_masked(q, k, v, mask, cfg)

            return block.attn_block(x, p, cfg, attend, real)
        x, stats, state, _ = block.rule_block(
            x, p, cfg, *layer_state(_SCOPE, cache["conv"], cache["ssm"], j),
            real)
        after.append(state)
        return x, stats

    x, stats = block.walk_layers(cfg, params, x, layer)
    with jax.named_scope(scopes.KV_POOL):
        for name, at in (("k", 0), ("v", 1)):
            if new_kv:
                cache[name] = lax.dynamic_update_slice(
                    cache[name], jnp.stack([kv[at] for kv in new_kv]),
                    (0, 0, 0, 0))
    if after:
        with jax.named_scope(_SCOPE):
            cache["conv"], cache["ssm"] = stacked(after)
    cache.update(start=start, pos=jnp.full((B,), T0, jnp.int32))
    return block.lm_logits(x[:, -1], params, cfg), \
        _counted(cache, cfg, stats)


def paged_prefill(block: Block, params, cache, tokens: jnp.ndarray, cfg, *,
                  row_bt: jnp.ndarray, prefix_len, n_tail, slot, state=None
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Prompt-tail ingestion for ONE sequence against the block pool
    (kv_decode.paged_prefill has the K/V half of the contract): tokens
    (1, Tt) RIGHT-aligned tail of `n_tail` real columns after
    `prefix_len` tokens whose attention-layer K/V are resident in
    `row_bt`'s blocks.

    The recurrent half is jamba_decode.jamba_paged_prefill's: `state` is
    int32 (3,) ``[source, snapshot entry, snapshot boundary]``.  The
    slot's state starts from zeros (``STATE_FROM_ZERO``), from its own
    rows (``STATE_FROM_SLOT``: the previous chunk of this prompt left
    them) or from snapshot entry ``source >= 0``, which has to be the
    state after exactly `prefix_len` tokens.  It ends as the state after
    ``prefix_len + n_tail`` tokens, in row `slot`.  With ``snapshot
    entry >= 0`` the state after ``snapshot boundary`` tokens
    (``prefix_len < boundary <= prefix_len + n_tail``) is also written
    into that entry of the snapshot pool.  None is a whole prompt from
    zeros, no snapshot."""
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
    # an attention layer's keys are the row's gathered view
    reach = prefill_reach(Tt, prefix_len, n_tail)
    # the column after which the state is `boundary` tokens old
    capture = jnp.clip(pad + boundary - prefix_len - 1, 0, Tt - 1)
    keep = jnp.maximum(entry, 0)
    # the slot's rows leave the big state ONCE, before the walk, and go
    # back once after it (decode_common.begin_rows has why)
    begin = begin_rows(_SCOPE, cache, slot, source)
    x = block.embed(params, tokens, cfg)                       # (1, Tt, d)
    ends, snaps = [], []

    def layer(x, p, kind, j):
        if kind == block.attn:
            def attend(q, k, v):
                nonlocal pools
                pools, (kview, vview) = pkv.attend(j, pools, k, v)
                return attend_banded(q[0], kview[0], vview[0], *reach, cfg,
                                     scopes.ATTN_FULL)[None]

            return block.attn_block(x, p, cfg, attend, real[None])
        x, stats, after, snap = block.rule_block(
            x, p, cfg, *layer_state(_SCOPE, *begin, j), real[None], capture)
        ends.append(after)
        snaps.append(snap)
        return x, stats

    x, stats = block.walk_layers(cfg, params, x, layer)
    # right-aligned: the last column is the last real one.  As eight
    # equal rows: the product of one row is compiled as a float32
    # multiply and sum over the whole head upcast
    # (kimi_k2_decode.kimi_k2_paged_prefill)
    logits = block.lm_logits(jnp.broadcast_to(x[0, -1], (8, cfg.d_model)),
                             params, cfg)[0]
    out = pkv.commit(pools)
    if ends:
        out.update(leave_rows(_SCOPE, cache, slot, ends, entry, keep, snaps))
    out["block_tables"] = cache["block_tables"].at[slot].set(row_bt)
    out["pos"] = cache["pos"].at[slot].set(prefix_len + n_tail)
    out["start"] = cache["start"].at[slot].set(0)
    return logits, _counted(out, cfg, stats)


def decode_step(block: Block, params, cache, tokens, cfg
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token per sequence: tokens (B,) int32, row b at cache slot
    ``cache["pos"][b]``.  Both cache layouts (decode_common.is_paged).
    A row with ``pos == 0`` holds no sequence that decodes (module
    docstring): it is routed to no expert, its recurrent state is left
    as it is and it stays at ``pos == 0``; what it computes is the
    masked garbage every family's idle rows produce.  An attention
    layer of a paged cache walks the pool where it lies on the chip
    (`banded_attention.attend_paged` picks).

    Returns (logits (B, padded_vocab) float32, updated cache)."""
    B = tokens.shape[0]
    paged = is_paged(cache)
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
    x = block.embed(params, tokens, cfg)[:, None]              # (B, 1, d)

    def layer(x, p, kind, j):
        if kind != block.attn:
            # the matrices go in and come back as the whole stack: layer
            # j's are updated where they lie (ops/kda.py kda_decode)
            x, stats, (window, held["ssm"]), _ = block.rule_block(
                x, p, cfg, layer_window(_SCOPE, held["conv"], j),
                held["ssm"], active[:, None], layer=j)
            held["conv"] = set_layer_window(_SCOPE, held["conv"], j, window)
            return x, stats

        def attend(q, k, v):
            q, k, v = q[:, 0], k[:, 0], v[:, 0]
            if paged:
                fresh.append((k, v))
                with jax.named_scope(scopes.ATTN_FULL):
                    return attend_paged(q, (held["k"], held["v"]), j,
                                        cache, (k, v), cfg)[:, None]
            with jax.named_scope(scopes.KV_POOL):
                for n, new in (("k", k), ("v", v)):
                    held[n] = held[n].at[j, rows, pos].set(new)
                view = (held["k"][j], held["v"][j])
            with jax.named_scope(scopes.ATTN_FULL):
                return attend_masked(q[:, None], *view, mask, cfg)

        return block.attn_block(x, p, cfg, attend, active[:, None])

    x, stats = block.walk_layers(cfg, params, x, layer)
    logits = block.lm_logits(x[:, 0], params, cfg)
    if paged:
        # the pools were read-only in the walk: the rows land now, every
        # attention layer at once (PagedKV.commit)
        out = pkv.commit(
            (held["k"], held["v"]),
            *(jnp.stack([kv[at] for kv in fresh])[:, :, None]
              for at in (0, 1))) if fresh else dict(cache)
    else:
        out = dict(cache, k=held["k"], v=held["v"])
    out.update(conv=held["conv"], ssm=held["ssm"])
    with jax.named_scope(scopes.KV_POOL):
        # a row without a sequence stays one: were its pos to count the
        # steps it idled through, the next step would route it and
        # advance its state
        out["pos"] = jnp.where(active, pos + 1, 0)
    return logits, _counted(out, cfg, stats)
