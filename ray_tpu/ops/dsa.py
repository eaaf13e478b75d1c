"""DeepSeek Sparse Attention's lightning indexer and its selection, in
``jnp`` (DeepSeek-V3.2-Exp's report and its reference ``inference/
model.py class Indexer``; models/glm_dsa.py projects what this module
scores).

A layer's indexer has ``J`` heads of ``D``.  From the layer's normed
input ``u`` and query latent ``c_q``, with the first ``r`` of each
``D`` rotated by the layer's rotary tables::

    q^I_{t,j} = RoPE_r((W^I_q c_q_t)_j)           J queries a token
    k^I_s     = RoPE_r(LayerNorm(W^I_k u_s))      ONE key a token: cached
    w_{t,j}   = (W^I_w u_t)_j * J^-1/2 * D^-1/2   float32
    I_{t,s}   = sum_j w_{t,j} * ReLU(q^I_{t,j} . k^I_s)     s <= t

and query ``t`` attends the ``min(topk, t + 1)`` positions ``s <= t``
of highest ``I_{t,.}`` alone (its own position competes like any
other).  The products ``q . k`` take bf16 operands and accumulate in
float32; the ReLU, the weighted sum over heads and the selection are
float32.

The selection is EXACT in both forms kept here, exactly ``count``
positions, and a tie at the last place goes to the LOWER position in
both, as the source's ``topk`` breaks it (a score of exactly 0, every
head's ReLU shut, is common where the heads are few):

* `select_top` (a decode step off the chip or over a dense cache,
  which needs the positions to gather): ``lax.top_k``, which is stable.
* `select_mask` (a prefill, and a decode step whose attention walks
  the paged pool under a mask, ops/mla_paged_decode.py: both need a
  mask and no index): each query's ``count``-th highest score, found
  by a bitwise search over float32's order (32 passes of
  compare-and-count, no sort); everything above it, and of the
  positions AT it the first so many that the count is met (a second
  search of the same kind, over the slot's bits).
  tests/test_dsa.py holds the two forms equal, tied scores among them.

No (T, S) score matrix is ever whole: `select_prefill` walks blocks of
queries, and each block's scores walk blocks of keys as far as the
block's last query reaches (`index_scores_block`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes

__all__ = ["index_scores", "index_scores_block", "index_scores_step",
           "select_mask", "select_top", "select_prefill", "selected_count",
           "pool_rows", "gather_selected"]


@jax.named_scope(scopes.ATTN_INDEX)
def index_scores(qi, w, k):
    """qi (..., T, J, D), w (..., T, J) float32, k (..., S, D) ->
    ``I`` (..., T, S) float32, every pair (what a caller may attend is
    its mask's)."""
    s = jnp.einsum("...tjd,...sd->...tjs", qi, k.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...tjs,...tj->...ts", jax.nn.relu(s),
                      w.astype(jnp.float32))


@jax.named_scope(scopes.ATTN_INDEX)
def index_scores_block(qi, w, k, top, kb: int):
    """A block of queries qi (Tq, J, D), w (Tq, J) against the keys k
    (S, D) of slots ``0 .. top`` (a traced scalar: the block's furthest
    reach), `kb` keys at a time: (Tq, S) float32, zeros past the last
    block walked.  The (Tq, J, kb) products are the largest thing
    alive."""
    Tq, S = qi.shape[0], k.shape[0]

    # (a loop's body names its scope again: it is lowered as a function
    # of its own, kimi_k2_decode.attend_blockwise)
    @jax.named_scope(scopes.ATTN_INDEX)
    def one(j, out):
        kj = lax.dynamic_slice_in_dim(k, j * kb, kb)
        return lax.dynamic_update_slice_in_dim(
            out, index_scores(qi, w, kj), j * kb, 1)

    return lax.fori_loop(0, (top + kb) // kb, one,
                         jnp.zeros((Tq, S), jnp.float32))


def index_scores_step(qi, w, pool, lidx, block_tables, slots, fresh):
    """One new token a row against the row's context in the paged index
    pool: qi (B, J, D), w (B, J); pool (L, blocks, bs, D) read through
    block_tables (B, nb) at layer `lidx`; `fresh` (B, D), the token's
    own key, stands at its slot ``slots`` (B,) of the gathered view
    (the pool gets it after the layers' scan).  Returns (B, nb * bs)
    float32 over every slot of the row's table."""
    B, nb = block_tables.shape
    with jax.named_scope(scopes.KV_POOL):
        view = pool[lidx, block_tables].reshape(B, nb * pool.shape[2], -1)
        view = view.at[jnp.arange(B), slots].set(fresh.astype(view.dtype),
                                                 mode="drop")
    return index_scores(qi[:, None], w[:, None], view)[:, 0]


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    u = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(0x80000000))


def selected_count(ok, topk: int):
    """How many of the reachable slots `ok` (..., S) a query attends:
    ``min(topk, reachable)`` (...,) int32."""
    return jnp.minimum(jnp.sum(ok, axis=-1, dtype=jnp.int32), topk)


@jax.named_scope(scopes.ATTN_INDEX)
def select_mask(scores, ok, topk: int):
    """(..., S) bool: the ``count`` reachable slots (`ok`) of highest
    score, ties to the lower slot.  No sort: the ``count``-th highest
    score's 32 bits are found from the top, each by counting the keys
    at or above a candidate.  A row that reaches nothing selects
    nothing."""
    keys = jnp.where(ok, _sortable(scores), jnp.uint32(0))
    count = selected_count(ok, topk)

    @jax.named_scope(scopes.ATTN_INDEX)
    def bit(i, t):
        cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        n = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(n >= count, cand, t)

    kth = lax.fori_loop(0, 32, bit, jnp.zeros(count.shape, jnp.uint32))
    kth = kth[..., None]
    above, at = keys > kth, keys == kth
    # of the slots AT the last place's score, the `spare` lowest: the
    # slot of the spare-th of them, found as the score was (a running
    # count along the row would do, and costs more than both searches:
    # XLA's cumsum of a (512, 12,800) block is 1 ms on this chip)
    spare = count - jnp.sum(above, axis=-1, dtype=jnp.int32)
    slot = jnp.arange(keys.shape[-1], dtype=jnp.int32)
    bits = max(keys.shape[-1] - 1, 1).bit_length()

    @jax.named_scope(scopes.ATTN_INDEX)
    def place(i, p):
        cand = p | (jnp.int32(1) << (bits - 1 - i))
        n = jnp.sum(at & (slot < cand[..., None]), axis=-1, dtype=jnp.int32)
        return jnp.where(n < spare, cand, p)

    last = lax.fori_loop(0, bits, place, jnp.zeros(count.shape, jnp.int32))
    return ok & (above | (at & (slot <= last[..., None])
                          & (spare > 0)[..., None]))


@jax.named_scope(scopes.ATTN_INDEX)
def select_top(scores, ok, topk: int):
    """The selected slots as indices: (idx (..., K) int32 by falling
    score, valid (..., K) bool), ``K = min(topk, S)``; the first
    ``count`` are the selection and the rest, unreachable or surplus,
    are not `valid`."""
    K = min(topk, scores.shape[-1])
    _, idx = lax.top_k(jnp.where(ok, scores, -jnp.inf), K)
    return idx.astype(jnp.int32), \
        jnp.arange(K) < selected_count(ok, topk)[..., None]


def select_prefill(qi, w, k, reach, topk: int, qb: int, kb: int):
    """One sequence's selection as a mask: queries qi (T, J, D), w
    (T, J) with furthest attendable slot ``reach`` (T,) (-1: a pad
    column, which selects nothing) over the sequence's index keys k
    (S, D), its own new rows among them.  Walks `qb` queries at a time
    and, under them, `kb` keys at a time up to the block's reach:
    (T, S) bool."""
    T, S = qi.shape[0], k.shape[0]
    slot = jnp.arange(S)

    def block(i):
        at = lax.dynamic_slice_in_dim(reach, i * qb, qb)
        scores = index_scores_block(
            lax.dynamic_slice_in_dim(qi, i * qb, qb),
            lax.dynamic_slice_in_dim(w, i * qb, qb), k, jnp.max(at), kb)
        return select_mask(scores, slot[None, :] <= at[:, None], topk)

    with jax.named_scope(scopes.ATTN_INDEX):
        return lax.map(block, jnp.arange(T // qb)).reshape(T, S)


@jax.named_scope(scopes.KV_POOL)
def pool_rows(pool):
    """A paged pool (L, blocks, bs, width) as rows (L, blocks * bs,
    width), for `gather_selected`: the ``jnp`` decode step's (since PR
    59 the chip's step walks the pool and gathers no row:
    models/glm_dsa_decode.py).  A pool whose rows are whole lane
    tiles is read where it lies and this is no operation.  A narrower
    one (a 64-wide rotary key) is stored block-minor on the chip, and a
    gather of rows has the compiler re-lay ALL of it first
    (decode_common.PagedKV has why): asked for ONCE a decode step for
    all layers (the pool is read-only in the step's layer scan), that
    copy was 1.48 ms a step for the GLM-5 cell's 0.29 GB while the chip
    ran this path, where a layer sliced out in every layer was 1.48 +
    1.79.  The copy is the compiler's and carries no name
    (`unscoped_time_share`); written down as a `pad` to whole lane
    tiles it was 1.4 ms slower and the copy stayed, and as
    `mla_paged_decode.rotary_lanes_reference`'s layers side by side 4.7
    ms slower (my chip runs, PR 58)."""
    L, blocks, bs, width = pool.shape
    return pool.reshape(L, blocks * bs, width)


@jax.named_scope(scopes.KV_POOL)
def gather_selected(rows, lidx, block_tables, idx, bs: int):
    """Rows of layer `lidx` of a pool laid as `pool_rows` lays it, at
    slots idx (B, K) of each row's block table (B, nb): (B, K, width),
    by (block table entry, offset)."""
    blk = jnp.take_along_axis(block_tables, idx // bs, axis=1)
    return rows[lidx, blk * bs + idx % bs]
