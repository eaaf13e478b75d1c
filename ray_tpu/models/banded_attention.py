"""Grouped-query attention over FOLDED K/V, for the families whose
layers differ in reach: the whole score matrix, a prefill's banded walk,
a window layer's ring, a decode column over the paged pool.

K and V of one token are one row each of ``kv_width`` = n_kv_head *
head_dim lanes; a K/V head is a lane slice of it (`_head`).  `cfg` is
any config with ``dtype``, ``n_kv_head`` and ``head_dim`` (and
``attn_block`` for `banded_walk`): a family with another geometry hands
a namespace of its own (models/phi4flash.py ``cfg.pairs``).  No family
is named here; four call it (models/laguna_decode.py,
models/phi4flash_decode.py, and through models/delta_decode.py
models/solar_open2_decode.py and models/olmo_hybrid_decode.py).

  * `attend_masked`: a row's queries against its own K/V under a mask,
    the whole score matrix: the full-sequence forward and the dense
    cache's programs, small sizes; `attn_out` its gated way back.
  * `attend_banded`: a prefill's attention without its score matrix.  A
    tile of queries walks the key tiles between its first query's
    window edge (or 0) and its own diagonal, on the chip as one kernel a
    layer (ops/banded_flash.py), off it in ``jnp`` (`banded_walk`).
    Window or full is data: `prefill_reach` lays it out, and
    `banded_prefill_attention` counts for the host what was walked.
  * a window layer's RING of its last ``window`` rows a slot: the row of
    cache slot ``s`` is ``s mod window``.  What a ring row holds is
    derived, not stored: at cache position ``p`` (the newest row
    written) row ``r`` holds slot ``p - ((p - r) mod window)``,
    attendable iff that is ``>= start`` (`_ring_mask`, `ring_after`).  A
    decode column over it is `attend_stacked_ring`: on the chip one
    kernel that reads each row's ring where it lies in the layers'
    stack (ops/ring_decode.py), else `attend_rows` over the layer's
    rings sliced out.
  * `attend_paged`: a decode column over the paged pool, and the ONE
    place that picks the kernel (ops/gqa_paged_decode.py) or its
    ``jnp`` reference by the backend.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.decode_common import _block_of
from ray_tpu.ops import banded_flash as flash
from ray_tpu.ops.gqa_paged_decode import (gqa_paged_decode,
                                          gqa_paged_decode_reference)
from ray_tpu.ops.ring_decode import fits_the_kernel, ring_decode

# -- the whole score matrix ---------------------------------------------------

def attend_masked(q, k, v, mask, cfg, scale=None):
    """q (B, T, H, hd) over folded k, v (B, S, kv_width) under mask (B,
    T, S): grouped queries, no head repeated; (B, T, H, hd).  The whole
    score matrix: the full-sequence forward and the dense cache's
    programs, small sizes.  `cfg`: any config with ``n_kv_head`` and
    ``dtype``; `scale`: the scores' factor where it is not ``1 /
    sqrt(hd)``."""
    B, T, H, hd = q.shape
    S, kv = k.shape[1], cfg.n_kv_head
    qg = q.reshape(B, T, kv, H // kv, hd)
    kh = k.reshape(B, S, kv, hd)
    vh = v.reshape(B, S, kv, hd)
    s = jnp.einsum("btkgd,bskd->bkgts", qg, kh).astype(jnp.float32)
    s = s / math.sqrt(hd) if scale is None else s * scale
    s = jnp.where(mask[:, None, None], s, -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bkgts,bskd->btkgd", probs, vh).reshape(B, T, H, hd)


def attn_out(o, gate, p, cfg):
    """o (..., H, hd) times its gates, (..., H) one a head or (..., H,
    hd) one a channel, through ``W_o``: (..., d)."""
    dt = cfg.dtype
    o = o.astype(jnp.float32)
    if gate.ndim < o.ndim:
        gate = gate[..., None]
    o = (o * gate).astype(dt)
    return o.reshape(*o.shape[:-2], -1) @ p["wo"].astype(dt).reshape(
        -1, cfg.d_model)


# -- a prefill's banded walk ---------------------------------------------------

def _head(x, g: int, cfg):
    """K/V head `g` of folded rows x (..., kv_width): a lane slice."""
    return x[..., g * cfg.head_dim:(g + 1) * cfg.head_dim]


def _rows_scores(q, k, cfg, scale=None):
    """Every row against its OWN keys: q (B, H, hd), k (B, S, kv_width)
    -> (B, H, S) float32, scaled (by ``1 / sqrt(head_dim)`` unless the
    caller states a `scale`)."""
    G = q.shape[1] // cfg.n_kv_head
    s = jnp.concatenate([
        jnp.einsum("bgd,bsd->bgs", q[:, g * G:(g + 1) * G],
                   _head(k, g, cfg), preferred_element_type=jnp.float32)
        for g in range(cfg.n_kv_head)], axis=1)
    return s / math.sqrt(cfg.head_dim) if scale is None else s * scale


def _rows_values(e, v, cfg):
    """Weights e (B, H, S) over each row's own values v (B, S,
    kv_width): (B, H, hd) float32."""
    G = e.shape[1] // cfg.n_kv_head
    e = e.astype(cfg.dtype)
    return jnp.concatenate([
        jnp.einsum("bgs,bsd->bgd", e[:, g * G:(g + 1) * G],
                   _head(v, g, cfg), preferred_element_type=jnp.float32)
        for g in range(cfg.n_kv_head)], axis=1)


def _takes_kernel(T: int, S: int, H: int, cfg) -> bool:
    """What a prefill can see of its input picks its attention: on the
    chip, heads of whole lanes over whole tiles take the kernel
    (ops/banded_flash.py); the CPU and any other shape keep
    `banded_walk`, the parity oracle."""
    return jax.default_backend() == "tpu" and flash.fits(
        T, S, H, cfg.n_kv_head, cfg.head_dim, cfg.n_kv_head * cfg.head_dim)


def attend_banded(q, k, v, first, last, cfg, scope: str, scale=None):
    """One sequence's attention without its score matrix (`cfg`: any
    config with ``dtype``, ``attn_block``, ``n_kv_head`` and
    ``head_dim``; `scale`: the scores' factor where it is not ``1 /
    sqrt(head_dim)``).  q (T, H,
    hd); k, v (S, kv_width) folded; query t attends the key INDICES
    ``first[t] <= a <= last[t]`` ((T,) int32; a query with ``last <
    first`` attends nothing and gives zeros).  Returns (T, H, hd) in
    the compute dtype.  Window or full is data (`first`, `last`), the
    group size, the head count and the scale are `cfg`'s and `q`'s, and
    `_takes_kernel` picks the path: one Pallas call under `scope`, or
    `banded_walk`."""
    T, H, hd = q.shape
    if _takes_kernel(T, k.shape[0], H, cfg):
        with jax.named_scope(scope):
            return flash.banded_flash(
                q, k, v, first, last, n_kv_head=cfg.n_kv_head, head_dim=hd,
                scale=1.0 / math.sqrt(hd) if scale is None else scale)
    return banded_walk(q, k, v, first, last, cfg, scope, scale)


def banded_walk(q, k, v, first, last, cfg, scope: str, scale=None):
    """`attend_banded` in ``jnp``, the CPU's path and the parity
    oracle: a tile of queries walks the key tiles from its lowest
    `first` to its highest `last` with a running maximum and sum, so a
    band costs its width and a causal triangle its half."""
    T, H, hd = q.shape
    S = k.shape[0]
    dt = cfg.dtype
    qb, kb = _block_of(cfg, T), _block_of(cfg, S)
    G = H // cfg.n_kv_head
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    empty = last < first
    first = jnp.where(empty, S, first)
    last = jnp.where(empty, -1, last)

    def queries(i):
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb)
        lo = lax.dynamic_slice_in_dim(first, i * qb, qb)
        hi = lax.dynamic_slice_in_dim(last, i * qb, qb)

        # (a loop's body names its scope again: it is lowered as a
        # function of its own, kimi_k2_decode.attend_blockwise)
        @jax.named_scope(scope)
        def over(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_slice_in_dim(k, j * kb, kb)
            vj = lax.dynamic_slice_in_dim(v, j * kb, kb)
            at = j * kb + jnp.arange(kb)
            ok = ((at[None, :] >= lo[:, None])
                  & (at[None, :] <= hi[:, None]))[None]     # (1, qb, kb)
            s = jnp.concatenate([
                jnp.einsum("qgd,kd->gqk", qi[:, g * G:(g + 1) * G],
                           _head(kj, g, cfg),
                           preferred_element_type=jnp.float32)
                for g in range(cfg.n_kv_head)], axis=0)     # (H, qb, kb)
            s = jnp.where(ok, s * scale, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            # a row with nothing to attend yet has m_new == -1e30 and
            # exp(0) == 1 on every masked key: zero them
            e = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
            shrink = jnp.exp(m - m_new)
            ed = e.astype(dt)
            out = jnp.concatenate([
                jnp.einsum("gqk,kd->gqd", ed[g * G:(g + 1) * G],
                           _head(vj, g, cfg),
                           preferred_element_type=jnp.float32)
                for g in range(cfg.n_kv_head)], axis=0)     # (H, qb, hd)
            return (m_new, l * shrink + jnp.sum(e, axis=-1),
                    acc * shrink[..., None] + out)

        _, l, acc = lax.fori_loop(
            jnp.minimum(jnp.min(lo), S) // kb, (jnp.max(hi) + kb) // kb,
            over, (jnp.full((H, qb), -1e30, jnp.float32),
                   jnp.zeros((H, qb), jnp.float32),
                   jnp.zeros((H, qb, hd), jnp.float32)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(1, 0, 2).astype(dt)

    with jax.named_scope(scope):
        return lax.map(queries, jnp.arange(T // qb)).reshape(T, H, hd)


def prefill_reach(t_pad: int, prefix_len, n_tail, window=None, xp=jnp):
    """(first, last) of `attend_banded` for a paged prefill's `t_pad`
    columns, the last `n_tail` of them real, behind `prefix_len` slots
    (a pad column's ``last`` is -1: it attends nothing).  A full layer
    (`window` None) attends the row's gathered view, index == slot.  A
    window layer's keys are laid so that index ``a`` holds slot ``a +
    low``: the ring's `window` slots before the tail, then the tail's
    own, the tail's pad columns under the ring's rows.  `xp` is
    jax.numpy in a program, numpy for the host's count."""
    pad = t_pad - n_tail
    col = xp.arange(t_pad, dtype=xp.int32)
    real = col >= pad
    logical = prefix_len + col - pad               # position iff real
    if window is None:
        return xp.zeros_like(logical), xp.where(real, logical, -1)
    low = prefix_len - pad - window
    return (xp.maximum(logical - window + 1, 0) - low,
            xp.where(real, logical - low, -1))


def banded_prefill_attention(cfg, t_pad: int, prefix_len: int, n_tail: int,
                             layers) -> Tuple[bool, int, int]:
    """For the host's count of what a paged prefill's attention layers
    ran (`families.Family.prefill_attention`): (whether the kernel
    attended every one, the (query tile, key tile) pairs they walked a
    K/V head, the pairs a walk over every key tile the sequence holds
    would have).  `cfg`: the geometry `attend_banded` is given;
    `layers`: (how many, query heads, rows of the view, window or None)
    of each kind.  A `jnp` prefill counts no pairs."""
    walked = square = 0
    for count, H, S, window in layers:
        if not _takes_kernel(t_pad, S, H, cfg):
            return False, 0, 0
        lo, hi, _, _ = flash.walk(
            *prefill_reach(t_pad, prefix_len, n_tail, window, xp=np), S)
        held = prefix_len if window is None else min(prefix_len, window)
        walked += count * int((hi - lo).sum())
        square += count * len(lo) * -(-(held + n_tail) // flash.BLOCK_K)
    return True, walked, square


# -- a window layer's ring ----------------------------------------------------

def _ring_mask(pos, start, window: int):
    """(B, window) bool: the ring rows row b attends once its row of
    slot ``pos[b]`` is written (module docstring)."""
    r = jnp.arange(window)
    held = pos[:, None] - (pos[:, None] - r[None, :]) % window
    return held >= start[:, None]


def ring_after(rows, end, window: int):
    """The ring after the slots ``[end - window, end)`` whose rows are
    ``rows[..., :window, :]`` in slot order: row r is the slot congruent
    to r.  (A slot below 0 gives a row that `_ring_mask` never
    shows.)"""
    return jnp.take(rows, (jnp.arange(window) - end) % window, axis=-2)


def attend_rows(q, k, v, mask, cfg, scale=None):
    """q (B, H, hd) over each row's OWN rows k, v (B, S, kv_width)
    under mask (B, S): (B, H, hd) in the compute dtype.  The whole
    score row: a ring, or a small dense cache."""
    s = jnp.where(mask[:, None], _rows_scores(q, k, cfg, scale), -1e30)
    probs = jax.nn.softmax(s, axis=-1)
    return _rows_values(probs, v, cfg).astype(cfg.dtype)


#: a window layer's decode column over each row's ring (B, window,
#: kv_width) under mask (B, window)
_attend_ring = jax.named_scope(scopes.ATTN_WINDOW)(attend_rows)


def attend_stacked_ring(q, rings, j, pos, start, cfg, scale=None):
    """A window layer's decode column over layer `j` (an index, may be
    traced) of the stacked rings (wk, wv), each (n_window, B, window,
    kv_width) with this column's rows written: q (B, H, hd) -> (B, H,
    hd).  What the program can see of its input picks the path, as a
    full layer's walk is picked: on the chip, where heads and rows are
    whole lanes and the window whole sublane tiles, the kernel reads
    each row's ring where it lies in the stack (ops/ring_decode.py);
    else the layer's rings are sliced out and attended by
    `attend_rows`, the CPU's path and the parity oracle."""
    if jax.default_backend() == "tpu" and fits_the_kernel(q, rings[0]):
        return ring_decode(
            q, *rings, j, pos, start, n_kv_head=cfg.n_kv_head,
            scale=1.0 / math.sqrt(cfg.head_dim) if scale is None else scale)
    window = rings[0].shape[2]
    with jax.named_scope(scopes.ATTN_WINDOW):
        mine = tuple(lax.dynamic_index_in_dim(r, j, 0, keepdims=False)
                     for r in rings)
        ring_mask = _ring_mask(pos, start, window)
    return _attend_ring(q, *mine, ring_mask, cfg, scale)


# -- a decode column over the paged pool --------------------------------------

def attend_paged(q, pools, f: int, cache, fresh, cfg, scale=None):
    """One decode column of every row over the paged grouped-query
    pool: q (B, H, hd); pools = the whole (K, V) pools; `f` the layer's
    place in them; `fresh` = this column's (k, v) (B, kv_width),
    attended beside the slots ``start <= s < pos``.  What the program
    can see of its input picks the path, HERE and nowhere else: on the
    chip the kernel walks each row's own blocks where they lie
    (ops/gqa_paged_decode.py); elsewhere the ``jnp`` reference over the
    gathered views, the CPU's path and the parity oracle.  Under the
    caller's scope; `cfg` and `scale` as `attend_rows` reads them (a
    family of pair-heads hands its own geometry and scale)."""
    walk = gqa_paged_decode if jax.default_backend() == "tpu" \
        else gqa_paged_decode_reference
    return walk(q, *pools, cache["block_tables"], cache["pos"], f, fresh,
                n_kv_head=cfg.n_kv_head,
                scale=1.0 / math.sqrt(cfg.head_dim) if scale is None
                else scale, start=cache["start"])
