"""One decode column of latent attention (MLA, absorbed) over a PAGED
latent pool, read where it lies.

A row b holds ``pos[b]`` cached positions in the blocks its table
names; per position ONE latent ``ckv`` (c wide) and ONE rotary key
``kpe`` (r wide), shared by all H heads.  With ``W_uk`` folded into the
query (``q_lat``, models/kimi_k2.attend_absorbed) the column's
attention is

  ``s[h, t] = (q_lat[h] . ckv[t] + q_rope[h] . kpe[t]) * scale``
  ``o_lat[h] = sum_t softmax(s[h])[t] ckv[t]``

over the slots ``start[b] <= t < pos[b]`` and the row's own new
position (`fresh`: not in the pool yet, `PagedKV.commit` lands it after
the layer scan); where a learned indexer has picked which of them a
row attends (`selected`: models/glm_dsa_decode.py), over those alone,
the own position among them or not.  Two bodies, one mathematics:

  * `mla_paged_decode_reference` -- pure ``jnp``: every row's blocks
    gathered to the dense-equivalent ``(B, max_blk * bs, width)`` views,
    masked scores, one softmax.  What the CPU runs and what the kernel
    is held to.
  * `mla_paged_decode` -- one ``pallas_call`` named ``mla_paged_decode``.
    The latent pool stays in HBM, WHOLE (every layer's blocks: the
    layer is a prefetched scalar, nothing is sliced out or gathered
    first); a grid step is one row and walks ``ceil(pos / bs)`` of its
    table's blocks, not the table, a chunk of `_CHUNK` blocks at a
    time: each block is one DMA from ``ckv[layer, table[b, j]]`` into
    a ring of `_RING` VMEM buffers (the next chunks' copies are in
    flight while this one is attended, across the rows' edges too: a
    row's last chunks start its successor's first), one wait a tensor
    answers for a chunk's copies, and the chunk's scores, its running
    maximum and sum and its weighted sum are taken from that buffer,
    so a latent crosses HBM once.

Precision: operands as stored (bf16), scores and sums accumulated in
float32, the softmax's statistics float32, probabilities cast to the
pool's dtype before the weighted sum (as `attend_absorbed` casts them).
The running softmax starts from the fresh key (maximum = its score,
sum = 1), so no row is ever empty: a row with ``pos == 0`` walks
nothing and returns its own new latent, as the masked path does.
Under a selection that leaves the own position out the row starts with
no maximum instead, and the first selected slot's shrinks what stood
in the sums before it to exactly 0 (a selection is never empty: a row
reaches its own position at least, and takes one of what it reaches).

The rotary keys cannot be read where they lie: a 64-wide pool is stored
block-minor by the chip's compiler (PERF.md, PR 32), a block's keys are
half a memory tile, and only whole tiles can be copied.  `rotary_lanes`
(a second, small kernel) re-lays the rotary pool ONCE a decode step,
all layers side by side along the lanes (two layers a 128-lane tile);
the attention kernel copies the tile that holds its layer and
multiplies it by a query that is zero under the other layer's lanes.

What the chip said of the walk (my chip run, PR 33; 64 rows of 2,048 to
8,448 positions, 64 heads, 6 layers a step): the copies alone 3.8 ms a
step (690 GB/s), the arithmetic alone 3.2, together 4.8; a copy costs
~13 ns of the scalar core to issue whatever its size, so 64-block
chunks beat 32 (5.1 against 5.6 ms) though a row's last chunk is
fetched whole, and 128 lose (5.9); two buffers or four do what three
do; the rotary keys' re-lay 1.56 ms a step.

And of the walk under a selection (my chip runs, PR 59; GLM-5's cell:
32 rows of 4,600 to 12,700 positions, 2,048 selected a row, 64 heads,
5 layers a step over a pool of 28,597 blocks): 2.94 ms a step with the
mask and 2.93 without (0.59 a layer; 585 GB/s of the 1.72 GB it reads:
the copies set the pace and the mask's row and its AND hide beside
them), where XLA's gathers of the 2,048 selected latents and rotary
keys a row, the block-table lookups of their slots, the 64-wide pool's
re-laying for them and the attention over the gathered rows took 12.7
(a gather moves 67 GB/s).  Walking every block to weigh a quarter of
the positions is the cheaper read while the selected are spread so
that nearly every block of 16 holds one; skipping the blocks and
chunks that hold none is for contexts where they do not (ROADMAP.md
B3a).  The same 13 ns a copy, 64-block chunks and ring of 3 were kept
as they stood: the step's walk reads what the unmasked one reads alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

#: blocks a chunk: 64 blocks of 16 positions are 1,024 slots, 1 MB of
#: bf16 latents a buffer
_CHUNK = 64
#: chunk buffers: one attended from, the others on their way in
_RING = 3
_LANES = 128
_MASKED = -1e30


def _own(selected, pos):
    """Whether each row's own new position, slot ``pos`` of its table,
    is among `selected` (B, slots): (B,) bool."""
    at = jnp.minimum(pos, selected.shape[1] - 1)[:, None]
    return jnp.take_along_axis(selected, at, axis=1)[:, 0] \
        & (pos < selected.shape[1])


def mla_paged_decode_reference(q_lat, q_rope, ckv, kpe, block_tables, pos,
                               lidx, fresh, *, scale, start=None,
                               selected=None):
    """q_lat (B, H, c), q_rope (B, H, r); ckv (L, blocks, bs, c) and
    kpe (L, blocks, bs, r) the whole pools, of which layer `lidx`;
    block_tables (B, max_blk), pos (B,), start (B,) or None (zeros);
    fresh = (ckv_new (B, c), kpe_new (B, r)); selected (B, max_blk *
    bs) bool or None: the slots of the row's table a learned indexer
    picked, slot ``pos`` (the own new position) among them or not; the
    others are not attended -> o_lat (B, H, c)."""
    B, nb = block_tables.shape
    bs = ckv.shape[2]
    dt = ckv.dtype
    cview = ckv[lidx, block_tables].reshape(B, nb * bs, -1)
    rview = kpe[lidx, block_tables].reshape(B, nb * bs, -1)
    slot = jnp.arange(nb * bs)[None]
    lo = 0 if start is None else start[:, None]
    ok = (slot >= lo) & (slot < pos[:, None])
    mine = jnp.ones((B, 1), bool)
    if selected is not None:
        ok, mine = ok & selected, _own(selected, pos)[:, None]

    def scores(c, r):                      # (B, S, width) -> (B, H, S)
        return (jnp.einsum("bhc,bsc->bhs", q_lat, c.astype(dt),
                           preferred_element_type=jnp.float32)
                + jnp.einsum("bhr,bsr->bhs", q_rope, r.astype(dt),
                             preferred_element_type=jnp.float32)) * scale

    s = jnp.concatenate(
        [jnp.where(ok[:, None], scores(cview, rview), _MASKED),
         jnp.where(mine[:, None], scores(fresh[0][:, None],
                                         fresh[1][:, None]), _MASKED)],
        axis=-1)
    probs = jax.nn.softmax(s, axis=-1).astype(dt)
    keys = jnp.concatenate([cview, fresh[0][:, None].astype(dt)], axis=1)
    return jnp.einsum("bhs,bsc->bhc", probs, keys,
                      preferred_element_type=jnp.float32).astype(dt)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _kernel(base_ref, tab_ref, pos_ref, start_ref, chunks_ref, next_ref,
            qlat_ref, qrope_ref, cnew_ref, rnew_ref, ckv_hbm, kpe_hbm,
            o_ref, cbuf, rbuf, sems, acc_ref, ring_ref, *, nb: int,
            scale: float, own_ref=None, sel_ref=None):
    """One row.  Prefetched scalars: the layer's first block in the
    latent pool and first lane in the rotary rows (2,); the block
    tables, flat, a row of null blocks after the last ((B + 1) * nb,);
    pos, start (B,); chunks a row and the next row that has any
    (B + 1,).  qlat (1, H, c), qrope (1, H, r), cnew (1, 1, c), rnew
    (1, 1, r) in VMEM; ckv_hbm (L * blocks, bs, c), kpe_hbm (blocks, bs,
    lanes) in HBM; scratch: a ring of chunk buffers of latents (ring,
    chunk, bs, c) and rotary keys (ring, chunk, bs, r), their DMA
    semaphores (2, ring), the weighted sum (H, c) float32, and the
    ring's state (3,) int32: chunks attended since the call began, the
    row and chunk the next start is for.  With a selection
    (`_selected_kernel`): own_ref (B,) int32 prefetched, whether the
    row's own new position is selected, and sel_ref (1, chunks, chunk *
    bs) int32 in VMEM, which slots of the row's table are."""
    from jax.experimental.pallas import tpu as pltpu

    b, rows = pl.program_id(0), pl.num_programs(0)
    ring, chunk, bs, r = rbuf.shape
    span = chunk * bs
    f32 = jnp.float32
    DONE, ROW, CHUNK = range(3)
    lanes = pl.ds(pl.multiple_of(base_ref[1], _LANES), r)

    def start_next(buf, half):
        """Half of the next chunk in the order the rows are walked on
        its way into buffer `buf`: a copy a block, every one of the
        chunk's (past the row's last block the table names the null
        block 0, attended under the mask), so that one wait a tensor
        answers for them all.  After the last row's last chunk the
        table's row of null blocks is fetched: no branch here, and
        `ring - 1` chunks are in flight whenever a chunk is attended.
        In halves, one before each of a chunk's two large products: the
        scalar core issues a half beside a product (4.83 ms a step
        where all the copies first take 5.16)."""
        row, i = ring_ref[ROW], ring_ref[CHUNK]
        at = row * nb + i * chunk
        for j in range(half * chunk // 2, (half + 1) * chunk // 2):
            blk = tab_ref[at + j]
            pltpu.make_async_copy(ckv_hbm.at[base_ref[0] + blk],
                                  cbuf.at[buf, j], sems.at[0, buf]).start()
            pltpu.make_async_copy(kpe_hbm.at[blk, :, lanes], rbuf.at[buf, j],
                                  sems.at[1, buf]).start()
        if half:
            last = i + 1 >= chunks_ref[row]
            ring_ref[CHUNK] = jnp.where(last, 0, i + 1)
            ring_ref[ROW] = jnp.where(last, next_ref[row], row)

    def wait(buf):
        """The oldest chunk in flight, buffer `buf`'s, has landed."""
        ring_ref[DONE] += 1
        # a wait counts the bytes of its destination, whatever the
        # source: the whole buffer is the chunk's copies together
        pltpu.make_async_copy(ckv_hbm.at[pl.ds(0, chunk)], cbuf.at[buf],
                              sems.at[0, buf]).wait()
        pltpu.make_async_copy(kpe_hbm.at[pl.ds(0, chunk), :, pl.ds(0, r)],
                              rbuf.at[buf], sems.at[1, buf]).wait()

    @pl.when(b == 0)
    def _first_row():
        ring_ref[DONE] = 0
        ring_ref[CHUNK] = 0
        ring_ref[ROW] = jnp.where(chunks_ref[0] > 0, 0, next_ref[0])
        for buf in range(ring - 1):
            start_next(buf, 0)
            start_next(buf, 1)

    n, lo = pos_ref[b], start_ref[b]
    q, qr = qlat_ref[0], qrope_ref[0]                  # (H, c), (H, r)
    cn, rn = cnew_ref[0], rnew_ref[0]                  # (1, c), (1, r)
    dt = cbuf.dtype
    # the row's own new position opens the running softmax
    m0 = (jnp.sum(q.astype(f32) * cn.astype(f32), axis=-1, keepdims=True)
          + jnp.sum(qr.astype(f32) * rn.astype(f32), axis=-1,
                    keepdims=True)) * scale            # (H, 1)
    acc_ref[...] = jnp.broadcast_to(cn.astype(dt).astype(f32),
                                    acc_ref.shape)
    if sel_ref is not None:
        # ... where it is selected.  Else the row opens with no maximum
        # yet, and what stands in the sums before its first selected
        # slot (the opening's own 1 and latent, exp(0) a masked slot of
        # the chunks before it) the first real maximum shrinks to
        # exactly 0: exp(_MASKED - m) is 0.0 in float32
        m0 = jnp.where(own_ref[b] > 0, m0, _MASKED)
    nt = (((1,), (1,)), ((), ()))                      # a @ b.T

    def attend(buf, i, m, l):
        """Chunk i of this row, landed in buffer `buf`."""
        wait(buf)
        # into the buffer the chunk before this one was attended from
        free = (buf + ring - 1) % ring
        start_next(free, 0)
        kc = cbuf[buf].reshape(span, cbuf.shape[-1])
        kr = rbuf[buf].reshape(span, r)
        s = (lax.dot_general(q, kc, nt, preferred_element_type=f32)
             + lax.dot_general(qr, kr, nt, preferred_element_type=f32)
             ) * scale                                 # (H, span)
        slot = i * span + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = (slot >= lo) & (slot < n)
        if sel_ref is not None:
            ok &= sel_ref[0, pl.ds(i, 1), :] > 0       # (1, span)
        s = jnp.where(ok, s, _MASKED)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # m_new >= the fresh key's score: a masked slot's exp is 0
        p = jnp.exp(s - m_new)
        shrink = jnp.exp(m - m_new)
        start_next(free, 1)
        acc_ref[...] = acc_ref[...] * shrink + jnp.dot(
            p.astype(dt), kc, preferred_element_type=f32)
        return m_new, l * shrink + jnp.sum(p, axis=-1, keepdims=True)

    def one(i, carry):
        # a branch a buffer: inside one every address is a constant,
        # and the chip issues the next chunk's copies beside this
        # chunk's arithmetic (with the buffer a variable it does them
        # one after the other: 7.3 ms a step where this took 5.1)
        return lax.switch(ring_ref[DONE] % ring,
                          [functools.partial(attend, k)
                           for k in range(ring)], i, *carry)

    _, l = lax.fori_loop(0, chunks_ref[b], one, (m0, jnp.ones_like(m0)))
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)

    @pl.when(b + 1 == rows)
    def _last_row():            # the null chunks behind the last one
        for _ in range(ring - 1):
            wait(ring_ref[DONE] % ring)


def _selected_kernel(base_ref, tab_ref, pos_ref, start_ref, chunks_ref,
                     next_ref, own_ref, qlat_ref, qrope_ref, cnew_ref,
                     rnew_ref, sel_ref, *rest, **static):
    """`_kernel` under a selection: one prefetched vector and one VMEM
    block more, where a `pallas_call` puts them."""
    _kernel(base_ref, tab_ref, pos_ref, start_ref, chunks_ref, next_ref,
            qlat_ref, qrope_ref, cnew_ref, rnew_ref, *rest,
            own_ref=own_ref, sel_ref=sel_ref, **static)


def _check_width(r: int) -> None:
    if _LANES % r and r % _LANES:
        raise ValueError(
            f"rotary keys {r} wide neither divide a {_LANES}-lane tile "
            f"nor fill whole ones: no layer's keys lie in one tile")


def rotary_lanes_reference(kpe):
    """The rotary pool (L, blocks, bs, r) as (blocks, bs, lanes): a
    position's keys of ALL layers side by side along the lanes, whole
    lane tiles, which is how `mla_paged_decode` reads them by block."""
    L, blocks, bs, r = kpe.shape
    _check_width(r)
    side = kpe.transpose(1, 2, 0, 3).reshape(blocks, bs, L * r)
    return jnp.pad(side, ((0, 0), (0, 0), (0, -(L * r) % _LANES)))


def _lanes_kernel(x_ref, o_ref):
    """x (L, bs, r, tile): a tile of blocks as the chip stores the
    rotary pool, blocks minor-most; o (tile, bs, lanes): L * r, and
    zeros as far as the last lane tile's end where the layers leave it
    part empty."""
    L, bs, r, tile = x_ref.shape
    empty = o_ref.shape[-1] - L * r
    spare = [jnp.zeros((empty, tile), x_ref.dtype)] if empty else []
    o_ref[...] = jnp.swapaxes(jnp.stack([
        jnp.concatenate([x_ref[layer, t] for layer in range(L)] + spare,
                        axis=0).T
        for t in range(bs)]), 0, 1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def rotary_lanes(kpe, *, interpret: bool = False):
    """`rotary_lanes_reference` as one Pallas call named
    ``mla_rotary_lanes``, once a decode step for all layers (the pool
    is read-only in the step's layer scan).  The chip stores the
    64-wide pool with its BLOCKS minor-most, so ``(L, bs, r, blocks)``
    is the stored order and costs nothing; the kernel turns 128 blocks
    at a time: one pass, 1.56 ms for the cell's 0.48 GB, where XLA's
    own transposes take two passes and two buffers (3.0 ms; a layer
    sliced out in every layer three passes, 5.2 ms a step; my chip
    run, PR 33).  Layers that leave the last lane tile part empty (five
    of 64) get zeros there from the kernel, with no padded copy of the
    pool before it: 1.06 ms for a five-layer pool of 0.29 GB, where the
    reference's two passes and pad took 3.02 and a sixth layer of zeros
    padded on first 2.13 (my chip run, PR 59).  Keys narrower than half
    a lane tile (a toy configuration) take the reference."""
    L, blocks, bs, r = kpe.shape
    _check_width(r)
    if (2 * r) % _LANES:
        return rotary_lanes_reference(kpe)
    tile = min(_LANES, blocks)
    lanes = -(-L * r // _LANES) * _LANES
    return pl.pallas_call(
        _lanes_kernel,
        grid=(pl.cdiv(blocks, tile),),
        in_specs=[pl.BlockSpec((L, bs, r, tile), lambda i: (0, 0, 0, i))],
        out_specs=pl.BlockSpec((tile, bs, lanes), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((blocks, bs, lanes), kpe.dtype),
        interpret=interpret,
        name=scopes.MLA_ROTARY_LANES,
    )(kpe.transpose(0, 2, 3, 1))


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def mla_paged_decode(q_lat, q_rope, ckv, kpe_lanes, block_tables, pos,
                     lidx, fresh, *, scale: float, start=None,
                     selected=None, interpret: bool = False):
    """`mla_paged_decode_reference`'s contract as one Pallas call, but
    for the rotary keys: `kpe_lanes` is ``rotary_lanes(kpe)``.  `lidx`
    may be traced (the call sits in a scan over layers).  With
    `selected` the walk is the same walk, every block of the row, under
    one more mask; without, the program holds the kernel as it was.
    ``interpret=True`` runs the kernel in the Pallas interpreter (the
    CPU tests)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, c = q_lat.shape
    nb = block_tables.shape[1]
    L, blocks, bs, _ = ckv.shape
    chunk = min(_CHUNK, blocks)
    dt = ckv.dtype
    i32 = jnp.int32
    lidx = jnp.asarray(lidx, i32)
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
    # the lane tile(s) of the rotary rows that hold this layer's keys;
    # the query's rotary part goes where they lie in it, zeros beside
    w = q_rope.shape[-1]
    _check_width(w)
    r = max(w, _LANES)
    lane0 = lidx * w // r * r

    def tile(a):                       # (..., r') -> (..., r) at the keys
        a = a.astype(kpe_lanes.dtype)
        return lax.dynamic_update_slice_in_dim(
            jnp.zeros((*a.shape[:-1], r), a.dtype), a, lidx * w - lane0,
            axis=a.ndim - 1)

    def row(width):
        return pl.BlockSpec((1, H, width), lambda b, *_: (b, 0, 0))

    def new(width):
        return pl.BlockSpec((1, 1, width), lambda b, *_: (b, 0, 0))

    kernel, scalars = _kernel, [jnp.stack([lidx * blocks, lane0]), tables,
                                pos, start, n_chunks, following]
    blocked = [(q_lat.astype(dt), row(c)), (tile(q_rope), row(r)),
               (fresh[0].astype(dt)[:, None], new(c)),
               (tile(fresh[1])[:, None], new(r))]
    if selected is not None:
        # a chunk's slots a row of the block: the walk takes chunk i's
        # by its index; the own position's flag beside the scalars
        kernel = _selected_kernel
        span = chunk * bs
        picked = jnp.pad(selected, ((0, 0), (0, (wide - nb) * bs)))
        scalars.append(_own(selected, pos).astype(i32))
        blocked.append((picked.astype(i32).reshape(B, wide // chunk, span),
                        pl.BlockSpec((1, wide // chunk, span),
                                     lambda b, *_: (b, 0, 0))))

    return pl.pallas_call(
        functools.partial(kernel, nb=wide, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=[spec for _, spec in blocked] + [
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row(c),
            scratch_shapes=[pltpu.VMEM((_RING, chunk, bs, c), dt),
                            pltpu.VMEM((_RING, chunk, bs, r),
                                       kpe_lanes.dtype),
                            pltpu.SemaphoreType.DMA((2, _RING)),
                            pltpu.VMEM((H, c), jnp.float32),
                            pltpu.SMEM((3,), i32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, c), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=scopes.MLA_PAGED_DECODE,
    )(*scalars, *(a for a, _ in blocked),
      ckv.reshape(L * blocks, bs, c), kpe_lanes)


__all__ = ["mla_paged_decode", "mla_paged_decode_reference",
           "rotary_lanes", "rotary_lanes_reference"]
