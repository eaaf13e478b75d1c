"""A token's row to the held experts' grouped matmuls and back: the
dispatch and the combine of models/experts.py, without a sort, a
gather or a scatter.

A chip that holds ``g`` of the routed experts meets, per token, the few
of its ``top_k`` choices that are its own: `loc` (N, K) names each
choice's place among the held (``g``: an expert elsewhere, or a row
that holds no token).  Grouped order puts expert 0's rows first, then
expert 1's, ..., each expert's rows by ascending token: the row of
assignment (t, k) is ``starts[loc[t, k]]`` plus the number of earlier
tokens that chose the same expert.  That prefix count is not computed
beforehand: both kernels WALK the tokens in order on the scalar core
with one cursor an expert (``cursor[e]`` starts at ``starts[e]``, the
exclusive running sum of the experts' counts), so an assignment's row
is its expert's cursor when the walk reaches it.  A token none of whose
choices is local (`cnt` == 0: three in four at 12 of 384 experts held)
costs one scalar read.

Both stream the tokens' ``(N, d)`` float32 matrix through VMEM a tile
of `tm` tokens a grid step, as the matrix lies (no copy of it is made
for them), and meet the grouped rows as SLABS ``(rows, d / 128, 128)``
in HBM (`slabs`, `rows_of`):

  * `moe_dispatch` -- one ``pallas_call`` named ``moe_dispatch``: each
    local assignment's token row is lifted out of the tile into a
    staged slab and copied by one DMA to ``xs[row - lo]``.  Slabs of
    `xs` no assignment lands in are left as they were (the grouped
    matmul gives them no group).
  * `moe_combine` -- one ``pallas_call`` named ``moe_combine``: the
    result tile starts as `base`'s (the shared experts' sum, or
    zeros), the tile's assignments' slabs ``ys[row - lo]`` are copied
    into a VMEM stage, and each is added to its token's row times its
    router weight, float32 all through.  The result takes `base`'s
    buffer and is written once a tile.

Why slabs: the chip stores a matrix in tiles of 8 rows by 128 columns
and copies whole tiles, so one row of ``(rows, d)`` cannot be copied
alone (the chip's compiler refuses the slice), while a row's slab is 7
whole tiles of its own at d = 7,168, 28 KB in one piece.  A row and its
slab are turned into one another in registers (a reshape of one row),
which the tile's own copies in and out of VMEM hide.  A bf16 row shares
its memory words with its neighbour whatever the shape: `moe_dispatch`
is handed the float32 rows the compute dtype is rounded from, and the
caller rounds what comes back.

Both take a window ``[lo, lo + rows)`` of the grouped order: an
assignment outside it moves its cursor and nothing else, so a layer
whose local rows overflow one row tile is walked once a tile
(models/experts.routed_experts) and nothing is dropped.

`dispatch_reference` / `combine_reference` are the same contracts in
plain ``jnp`` (a stable sort, a gather, a scatter-add): what the
kernels are held to, and what runs off the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

_LANES = 128
#: rows of grouped results a combine tile keeps in flight
_STAGE = 64
#: bytes of one (tokens, d) float32 block of the combine's result
_BLOCK_BYTES = 4 << 20


def _grouped_rows(loc, starts, n_held: int):
    """(token (A,), choice (A,), row (A,)) of every assignment in
    grouped order; assignments on no held expert come last."""
    N, K = loc.shape
    flat = loc.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    e = flat[order]
    first = jnp.append(starts, 0)[jnp.minimum(e, n_held)]
    rank = jnp.arange(N * K) - jnp.searchsorted(e, e, side="left")
    row = jnp.where(e < n_held, first + rank, -1)
    return order // K, order % K, row


def slabs(a):
    """(rows, d) -> (rows, d / 128, 128): a row as whole memory tiles
    (a d that is no multiple of 128, a toy's: one short sublane)."""
    return a.reshape(a.shape[0], *_slab_shape(a.shape[1]))


def rows_of(a):
    """`slabs` undone."""
    return a.reshape(a.shape[0], -1)


def dispatch_reference(x, loc, starts, lo, *, rows: int):
    """x (N, d), loc (N, K) int32 in 0..g, starts (g,) -> xs (rows, s,
    l) slabs: ``xs[r - lo] = x[t]`` for each assignment whose grouped
    row r lies in ``[lo, lo + rows)``; other slabs zeros."""
    tok, _, row = _grouped_rows(loc, starts, starts.shape[0])
    at = jnp.where(row >= 0, row - lo, -1)
    at = jnp.where((at >= 0) & (at < rows), at, rows)       # dropped
    return slabs(jnp.zeros((rows, x.shape[1]), x.dtype).at[at].set(
        x[tok], mode="drop"))


def combine_reference(base, ys, loc, w, starts, lo):
    """base (N, d) float32, ys (rows, s, l) float32 slabs, w (N, K)
    float32 -> ``base[t] + sum_k w[t, k] ys[row(t, k) - lo]`` over the
    assignments inside the window, (N, d)."""
    rows = ys.shape[0]
    tok, k, row = _grouped_rows(loc, starts, starts.shape[0])
    at = row - lo
    ok = (row >= 0) & (at >= 0) & (at < rows)
    add = jnp.where(ok[:, None], rows_of(ys)[jnp.clip(at, 0, rows - 1)]
                    * w[tok, k][:, None], 0.0)
    return base.at[tok].add(add)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _walk(meta_ref, loc_ref, cnt_ref, cur_ref, *, tm: int, top_k: int,
          n_held: int, rows: int, hit, carry):
    """This grid step's `tm` tokens in order.  ``hit(t, k, r, carry)``
    for each local assignment (token t of all N, choice k) whose
    grouped row, less the window's start, is ``0 <= r < rows``.  The
    cursors live in SMEM across the grid's steps (the grid is walked in
    order: ``arbitrary``)."""
    @pl.when(pl.program_id(0) == 0)
    def _first():
        def put(e, _):
            cur_ref[e] = meta_ref[e]
            return _
        lax.fori_loop(0, n_held, put, 0)

    lo = meta_ref[n_held]
    first = pl.program_id(0) * tm

    def token(t, carry):
        t = first + t

        def local(carry):
            for k in range(top_k):
                e = loc_ref[t * top_k + k]

                def mine(carry, e=e, k=k):
                    r = cur_ref[e] - lo
                    cur_ref[e] = cur_ref[e] + 1
                    return lax.cond((r >= 0) & (r < rows),
                                    lambda c: hit(t, k, r, c),
                                    lambda c: c, carry)

                carry = lax.cond(e < n_held, mine, lambda c: c, carry)
            return carry

        return lax.cond(cnt_ref[t] > 0, local, lambda c: c, carry)

    return lax.fori_loop(0, tm, token, carry)


def _await(copy, n):
    """`n` copies of `copy`'s size, started on its semaphore, are done
    (a wait counts its destination's bytes, whatever the source)."""
    def one(_, c):
        copy.wait()
        return c

    lax.fori_loop(0, n, one, 0)


def _dispatch_kernel(meta_ref, loc_ref, cnt_ref, x_ref, xs_hbm, stage,
                     sem, cur_ref, *, tm, top_k, n_held, rows):
    """meta (g + 1,) = starts and the window's start, loc (N * K,), cnt
    (N,): prefetched, of all tokens; x (tm, d) this step's tokens in
    VMEM; xs (rows, s, l) in HBM; scratch: the stage (S, s, l), a DMA
    semaphore, the cursors (g,) in SMEM."""
    from jax.experimental.pallas import tpu as pltpu

    room, s, l = stage.shape
    first = pl.program_id(0) * tm

    def slab_copy(slot, r):
        return pltpu.make_async_copy(stage.at[slot], xs_hbm.at[r], sem)

    def drain(n):
        """The n copies in flight have left the stage."""
        _await(slab_copy(0, 0), n)
        return jnp.int32(0)

    def send(t, k, r, n):
        n = lax.cond(n == room, drain, lambda n: n, n)
        stage[n] = x_ref[pl.ds(t - first, 1), :].reshape(s, l)
        slab_copy(n, r).start()
        return n + 1

    drain(_walk(meta_ref, loc_ref, cnt_ref, cur_ref, tm=tm, top_k=top_k,
                n_held=n_held, rows=rows, hit=send, carry=jnp.int32(0)))


def _combine_kernel(meta_ref, loc_ref, cnt_ref, w_ref, ys_hbm, base_ref,
                    o_ref, stage, sem, cur_ref, tok_ref, wt_ref, *, tm,
                    top_k, n_held, rows):
    """As `_dispatch_kernel`, and w (N * K,) float32 prefetched; ys
    (rows, s, l) float32 in HBM; base and o (tm, d) float32 blocks;
    scratch: the stage (S, s, l) float32, and each staged slab's token
    and weight (S,) in SMEM."""
    from jax.experimental.pallas import tpu as pltpu

    room = stage.shape[0]
    d = o_ref.shape[1]
    first = pl.program_id(0) * tm

    def slab_copy(r, slot):
        return pltpu.make_async_copy(ys_hbm.at[r], stage.at[slot], sem)

    def flush(n):
        """The n staged slabs, landed, onto their tokens' rows."""
        _await(slab_copy(0, 0), n)

        def add(slot, c):
            at = pl.ds(tok_ref[slot], 1)
            o_ref[at, :] = o_ref[at, :] \
                + wt_ref[slot] * stage[slot].reshape(1, d)
            return c

        lax.fori_loop(0, n, add, 0)
        return jnp.int32(0)

    def fetch(t, k, r, n):
        # a token's choices may not all fit what is left of the stage
        n = lax.cond(n == room, flush, lambda n: n, n)
        slab_copy(r, n).start()
        tok_ref[n] = t - first
        wt_ref[n] = w_ref[t * top_k + k]
        return n + 1

    o_ref[...] = base_ref[...]
    flush(_walk(meta_ref, loc_ref, cnt_ref, cur_ref, tm=tm, top_k=top_k,
                n_held=n_held, rows=rows, hit=fetch,
                carry=jnp.int32(0)))


def token_tile(n: int, d: int) -> int:
    """Tokens a grid step: the largest power of two, at most 256, that
    divides `n` and whose (tokens, d) float32 block stays within
    `_BLOCK_BYTES`."""
    tm = 256
    while tm > 1 and (tm * d * 4 > _BLOCK_BYTES or n % tm):
        tm //= 2
    return tm


def _routing(loc, starts, lo):
    """The walk's operands: meta, loc flat, cnt."""
    i32 = jnp.int32
    g = starts.shape[0]
    meta = jnp.append(starts.astype(i32), jnp.asarray(lo, i32))
    cnt = jnp.sum(loc < g, axis=-1, dtype=i32)
    return meta, loc.astype(i32).reshape(-1), cnt


def _slab_shape(d: int):
    lanes = _LANES if d % _LANES == 0 else d
    return d // lanes, lanes


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def moe_dispatch(x, loc, starts, lo, *, rows: int,
                 interpret: bool = False):
    """`dispatch_reference`'s contract as one Pallas call, but for the
    slabs of `xs` no assignment lands in: those are whatever the buffer
    held.  x (N, d) of a 32-bit dtype; `lo` may be traced."""
    from jax.experimental.pallas import tpu as pltpu

    N, d = x.shape
    K, g = loc.shape[1], starts.shape[0]
    if x.dtype.itemsize != 4:
        raise ValueError(f"rows of {x.dtype} cannot be copied alone: "
                         f"hand the 32-bit rows they are rounded from")
    tm = token_tile(N, d)
    s, l = _slab_shape(d)
    return pl.pallas_call(
        functools.partial(_dispatch_kernel, tm=tm, top_k=K, n_held=g,
                          rows=rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N // tm,),
            in_specs=[pl.BlockSpec((tm, d), lambda i, *_: (i, 0))],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((_STAGE, s, l), x.dtype),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SMEM((g,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((rows, s, l), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * _BLOCK_BYTES + _STAGE * d * 4
            + (8 << 20)),
        interpret=interpret,
        name=scopes.MOE_DISPATCH,
    )(*_routing(loc, starts, lo), x)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_combine(base, ys, loc, w, starts, lo, *, interpret: bool = False):
    """`combine_reference`'s contract as one Pallas call: base (N, d),
    ys (rows, s, l) slabs; the result takes `base`'s buffer where the
    caller is done with it."""
    from jax.experimental.pallas import tpu as pltpu

    N, d = base.shape
    K, g = loc.shape[1], starts.shape[0]
    f32 = jnp.float32
    tm = token_tile(N, d)
    block = pl.BlockSpec((tm, d), lambda i, *_: (i, 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tm=tm, top_k=K, n_held=g,
                          rows=ys.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tm,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), block],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((_STAGE,) + ys.shape[1:], f32),
                            pltpu.SemaphoreType.DMA(()),
                            pltpu.SMEM((g,), jnp.int32),
                            pltpu.SMEM((_STAGE,), jnp.int32),
                            pltpu.SMEM((_STAGE,), f32)]),
        out_shape=jax.ShapeDtypeStruct((N, d), f32),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=4 * _BLOCK_BYTES + _STAGE * d * 4
            + (8 << 20)),
        interpret=interpret,
        name=scopes.MOE_COMBINE,
    )(*_routing(loc, starts, lo), w.astype(f32).reshape(-1),
      ys.astype(f32), base.astype(f32))


__all__ = ["moe_dispatch", "moe_combine", "dispatch_reference",
           "combine_reference", "slabs", "rows_of"]
