"""One decode column of grouped-query attention over a PAGED K/V pool,
read where it lies.

A row b holds ``pos[b]`` cached positions in the blocks its table
names; per position one K row and one V row of ``n_kv_head * hd``
lanes, the K/V heads side by side (models/laguna_decode.py folds them
so: a K/V head is a lane slice).  Query head h attends K/V head
``h // G``, ``G = H / n_kv_head``:

  ``s[h, t] = q[h] . k[t, h // G] * scale``
  ``o[h] = sum_t softmax(s[h])[t] v[t, h // G]``

over the slots ``start[b] <= t < pos[b]`` and the row's own new
position (`fresh`: not in the pool yet, `PagedKV.commit` lands it after
the layers).  Two bodies, one mathematics:

  * `gqa_paged_decode_reference` -- pure ``jnp``: every row's blocks
    gathered to the dense-equivalent ``(B, max_blk * bs, width)`` views,
    masked scores, one softmax.  What the CPU runs and what the kernel
    is held to.
  * `gqa_paged_decode` -- one ``pallas_call`` named ``gqa_paged_decode``.
    Both pools stay in HBM, WHOLE (every full layer's blocks: the layer
    is a prefetched scalar, nothing is sliced out or gathered first); a
    grid step is one row and walks ``ceil(pos / bs)`` of ITS table's
    blocks, not the wave's longest context and not the table, a chunk
    of `_CHUNK` blocks at a time: each block is one DMA of K and one of
    V into a ring of `_RING` VMEM buffers (the next chunks' copies are
    in flight while this one is attended, across the rows' edges too: a
    row's last chunks start its successor's first), one wait a tensor
    answers for a chunk's copies, and for each K/V head the group's
    scores, their running maximum and sum and the weighted sum are
    taken from the head's lanes of that buffer, so a position crosses
    HBM once.

Precision: operands as stored (bf16), scores and sums accumulated in
float32, the softmax's statistics float32, probabilities cast to the
pool's dtype before the weighted sum (as `laguna_decode._rows_values`
casts them).  The running softmax starts from the fresh key (maximum =
its score, sum = 1), so no row is ever empty: a row with ``pos == 0``
walks nothing and returns its own new value.

The walk (flat tables with a row of null blocks behind them, the ring's
state, a chunk's `start_next` / `wait`, the hand-over between rows) is
ops/mla_paged_decode.py's, written a second time: that kernel's
compiled module carries its source's path and lines, so a walk shared
between the two files cannot leave it the module it is (ROADMAP.md C8).

What the chip said of the walk (my chip run, PR 43; 64 rows of 256 to
8,704 positions, mean 4,316, 48 heads over 8 K/V heads of 128, both
full layers a step, 2.26 GB to read): 3.26 ms a step, 695 GB/s, where
the chunked gather took 11.21 (8.59 of gathers, 2.56 of scores); the
copies set the pace and the arithmetic hides behind them, so how a
chunk's 64 copies are issued (at once, in halves, a few before each
head's products) changes nothing (3.25-3.26); chunks of 64 blocks lose
(3.46: a row's last chunk is fetched whole) and chunks of 16 too
(3.78); a ring of two loses (3.86), four do what three do.  Every row
at 8,704: 726 GB/s; every row at 256: 0.43 ms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

#: blocks a chunk: 32 blocks of 16 positions are 512 slots, 1 MB of
#: bf16 keys and 1 MB of values a buffer at 1,024 lanes
_CHUNK = 32
#: chunk buffers: one attended from, the others on their way in
_RING = 3
#: a K/V head's group of query heads is padded to whole sublane tiles
_SUBLANES = 8
_MASKED = -1e30


def gqa_paged_decode_reference(q, kpool, vpool, block_tables, pos, f,
                               fresh, *, n_kv_head: int, scale: float,
                               start=None):
    """q (B, H, hd); kpool, vpool (n_full, blocks, bs, n_kv_head * hd)
    the whole pools, of which layer `f`; block_tables (B, max_blk), pos
    (B,), start (B,) or None (zeros); fresh = (k_new, v_new) each (B,
    n_kv_head * hd) -> (B, H, hd) in the pool's dtype."""
    B, H, hd = q.shape
    nb = block_tables.shape[1]
    bs = kpool.shape[2]
    dt = kpool.dtype
    G = H // n_kv_head
    slot = jnp.arange(nb * bs)[None]
    lo = 0 if start is None else start[:, None]
    ok = (slot >= lo) & (slot < pos[:, None])
    ok = jnp.pad(ok, ((0, 0), (0, 1)), constant_values=True)[:, None]

    def heads(pool, new):           # -> (B, S + 1, n_kv_head, hd)
        with jax.named_scope(scopes.KV_POOL):
            view = pool[f, block_tables].reshape(B, nb * bs, -1)
        view = jnp.concatenate([view, new[:, None].astype(dt)], axis=1)
        return view.reshape(B, nb * bs + 1, n_kv_head, hd)

    k, v = heads(kpool, fresh[0]), heads(vpool, fresh[1])
    qg = q.astype(dt).reshape(B, n_kv_head, G, hd)
    s = jnp.einsum("bngd,bsnd->bngs", qg, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(ok[:, None], s, _MASKED)
    probs = jax.nn.softmax(s, axis=-1).astype(dt)
    out = jnp.einsum("bngs,bsnd->bngd", probs, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, hd).astype(dt)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _kernel(base_ref, tab_ref, pos_ref, start_ref, chunks_ref, next_ref,
            q_ref, knew_ref, vnew_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
            sems, acc_ref, ring_ref, *, nb: int, scale: float):
    """One row.  Prefetched scalars: the layer's first block in the
    pools (1,); the block tables, flat, a row of null blocks after the
    last ((B + 1) * nb,); pos, start (B,); chunks a row and the next row
    that has any (B + 1,).  q (1, n_kv, Gp, hd) (a K/V head's group of
    query heads, zero rows up to a whole sublane tile), knew, vnew (1,
    1, n_kv * hd) in VMEM; k_hbm, v_hbm (n_full * blocks, bs, n_kv * hd)
    in HBM; scratch: a ring of chunk buffers of keys and of values
    (ring, chunk, bs, n_kv * hd), their DMA semaphores (2, ring), the
    weighted sums (n_kv, Gp, hd) float32, and the ring's state (3,)
    int32: chunks attended since the call began, the row and chunk the
    next start is for."""
    from jax.experimental.pallas import tpu as pltpu

    b, rows = pl.program_id(0), pl.num_programs(0)
    ring, chunk, bs, _ = kbuf.shape
    n_kv, Gp, hd = acc_ref.shape
    span = chunk * bs
    f32 = jnp.float32
    DONE, ROW, CHUNK = range(3)

    def start_next(buf):
        """The next chunk in the order the rows are walked on its way
        into buffer `buf`: a copy of K and one of V a block, every one
        of the chunk's (past the row's last block the table names the
        null block 0, attended under the mask), so that one wait a
        tensor answers for them all.  After the last row's last chunk
        the table's row of null blocks is fetched: no branch here, and
        `ring - 1` chunks are in flight whenever a chunk is attended.
        All at once: spread over the K/V heads' products, in halves as
        ops/mla_paged_decode.py issues its own or a few before each
        head's, they take the same time (module docstring)."""
        row, i = ring_ref[ROW], ring_ref[CHUNK]
        at = row * nb + i * chunk
        for j in range(chunk):
            blk = base_ref[0] + tab_ref[at + j]
            pltpu.make_async_copy(k_hbm.at[blk], kbuf.at[buf, j],
                                  sems.at[0, buf]).start()
            pltpu.make_async_copy(v_hbm.at[blk], vbuf.at[buf, j],
                                  sems.at[1, buf]).start()
        last = i + 1 >= chunks_ref[row]
        ring_ref[CHUNK] = jnp.where(last, 0, i + 1)
        ring_ref[ROW] = jnp.where(last, next_ref[row], row)

    def wait(buf):
        """The oldest chunk in flight, buffer `buf`'s, has landed."""
        ring_ref[DONE] += 1
        # a wait counts the bytes of its destination, whatever the
        # source: the whole buffer is the chunk's copies together
        pltpu.make_async_copy(k_hbm.at[pl.ds(0, chunk)], kbuf.at[buf],
                              sems.at[0, buf]).wait()
        pltpu.make_async_copy(v_hbm.at[pl.ds(0, chunk)], vbuf.at[buf],
                              sems.at[1, buf]).wait()

    @pl.when(b == 0)
    def _first_row():
        ring_ref[DONE] = 0
        ring_ref[CHUNK] = 0
        ring_ref[ROW] = jnp.where(chunks_ref[0] > 0, 0, next_ref[0])
        for buf in range(ring - 1):
            start_next(buf)

    n, lo = pos_ref[b], start_ref[b]
    dt = kbuf.dtype
    lanes = [pl.ds(g * hd, hd) for g in range(n_kv)]
    # the row's own new position opens the running softmax
    m0 = []
    for g in range(n_kv):
        kn = knew_ref[0, :, lanes[g]].astype(f32)          # (1, hd)
        m0.append(jnp.sum(q_ref[0, g].astype(f32) * kn, axis=-1,
                          keepdims=True) * scale)          # (Gp, 1)
        acc_ref[g] = jnp.broadcast_to(
            vnew_ref[0, :, lanes[g]].astype(f32), (Gp, hd))
    nt = (((1,), (1,)), ((), ()))                          # a @ b.T

    def attend(buf, i, ms, ls):
        """Chunk i of this row, landed in buffer `buf`."""
        wait(buf)
        # into the buffer the chunk before this one was attended from
        start_next((buf + ring - 1) % ring)
        slot = i * span + lax.broadcasted_iota(jnp.int32, (Gp, span), 1)
        ok = (slot >= lo) & (slot < n)
        ms_new, ls_new = [], []
        for g in range(n_kv):
            kg = kbuf[buf, :, :, lanes[g]].reshape(span, hd)
            s = lax.dot_general(q_ref[0, g], kg, nt,
                                preferred_element_type=f32) * scale
            s = jnp.where(ok, s, _MASKED)                  # (Gp, span)
            m = ms[g]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            # m_new >= the fresh key's score: a masked slot's exp is 0
            p = jnp.exp(s - m_new)
            shrink = jnp.exp(m - m_new)
            vg = vbuf[buf, :, :, lanes[g]].reshape(span, hd)
            acc_ref[g] = acc_ref[g] * shrink + jnp.dot(
                p.astype(dt), vg, preferred_element_type=f32)
            ms_new.append(m_new)
            ls_new.append(ls[g] * shrink
                          + jnp.sum(p, axis=-1, keepdims=True))
        return tuple(ms_new), tuple(ls_new)

    def one(i, carry):
        # a branch a buffer: inside one every address is a constant,
        # and the chip issues the next chunk's copies beside this
        # chunk's arithmetic (ops/mla_paged_decode.py)
        return lax.switch(ring_ref[DONE] % ring,
                          [functools.partial(attend, k)
                           for k in range(ring)], i, *carry)

    _, ls = lax.fori_loop(
        0, chunks_ref[b], one,
        (tuple(m0), tuple(jnp.ones_like(m) for m in m0)))
    for g in range(n_kv):
        o_ref[0, g] = (acc_ref[g] / ls[g]).astype(o_ref.dtype)

    @pl.when(b + 1 == rows)
    def _last_row():            # the null chunks behind the last one
        for _ in range(ring - 1):
            wait(ring_ref[DONE] % ring)


@functools.partial(jax.jit,
                   static_argnames=("n_kv_head", "scale", "interpret"))
def gqa_paged_decode(q, kpool, vpool, block_tables, pos, f, fresh, *,
                     n_kv_head: int, scale: float, start=None,
                     interpret: bool = False):
    """`gqa_paged_decode_reference`'s contract as one Pallas call.  `f`
    may be traced.  ``interpret=True`` runs the kernel in the Pallas
    interpreter (the CPU tests)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    nb = block_tables.shape[1]
    n_full, blocks, bs, width = kpool.shape
    G = H // n_kv_head
    Gp = -(-G // _SUBLANES) * _SUBLANES
    chunk = min(_CHUNK, blocks)
    dt = kpool.dtype
    i32 = jnp.int32
    pos = jnp.minimum(pos.astype(i32), nb * bs)     # no slot past the table
    start = jnp.zeros((B,), i32) if start is None else start.astype(i32)
    # what the walk reads of the tables, made once for all it reads: a
    # row's entries past its last block name the null block, a chunk's
    # worth of columns past the table and a row past the last too
    held = -(-pos // bs)
    n_chunks = jnp.append(-(-held // chunk), 0)                # (B + 1,)
    wide = -(-nb // chunk) * chunk
    tables = jnp.where(jnp.arange(wide)[None] < held[:, None],
                       jnp.pad(block_tables.astype(i32),
                               ((0, 0), (0, wide - nb))), 0)
    tables = jnp.pad(tables, ((0, 1), (0, 0))).reshape(-1)
    busy = jnp.where(n_chunks > 0, jnp.arange(B + 1), B)
    after = lax.cummin(busy, reverse=True)        # first busy row >= r
    following = jnp.append(after[1:], B)          # ... > r
    # a K/V head's query heads as a tile of their own: (n_kv, Gp, hd)
    grouped = jnp.pad(q.astype(dt).reshape(B, n_kv_head, G, hd),
                      ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    def group():
        return pl.BlockSpec((1, n_kv_head, Gp, hd),
                            lambda b, *_: (b, 0, 0, 0))

    def new():
        return pl.BlockSpec((1, 1, width), lambda b, *_: (b, 0, 0))

    out = pl.pallas_call(
        functools.partial(_kernel, nb=wide, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B,),
            in_specs=[group(), new(), new(),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=group(),
            scratch_shapes=[pltpu.VMEM((_RING, chunk, bs, width), dt),
                            pltpu.VMEM((_RING, chunk, bs, width), dt),
                            pltpu.SemaphoreType.DMA((2, _RING)),
                            pltpu.VMEM((n_kv_head, Gp, hd), jnp.float32),
                            pltpu.SMEM((3,), i32)]),
        out_shape=jax.ShapeDtypeStruct((B, n_kv_head, Gp, hd), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the ring, and room for a chunk's scores and the rest
            vmem_limit_bytes=2 * _RING * chunk * bs * width * dt.itemsize
            + (8 << 20)),
        interpret=interpret,
        name=scopes.GQA_PAGED_DECODE,
    )((jnp.asarray(f, i32) * blocks)[None], tables, pos, start, n_chunks,
      following, grouped, fresh[0].astype(dt)[:, None],
      fresh[1].astype(dt)[:, None],
      kpool.reshape(n_full * blocks, bs, width),
      vpool.reshape(n_full * blocks, bs, width))
    return out[:, :, :G].reshape(B, H, hd)


__all__ = ["gqa_paged_decode", "gqa_paged_decode_reference"]
