"""One sequence's banded grouped-query attention for a prefill: a
forward-only flash kernel whose band is data and whose K/V heads are
lane slices of folded rows.

`banded_flash` has `models/laguna_decode.attend_banded`'s contract to
the letter, and that function is what the kernel is held to
(tests/test_banded_flash.py): q (T, H, hd); k, v (S, n_kv_head * hd),
K and V of one slot folded into one row, a K/V head a slice of ``hd``
lanes; query ``t`` attends the key INDICES ``first[t] <= a <= last[t]``
and a query with ``last < first`` attends nothing and gives zeros.  A
causal triangle, a triangle behind a prefix, a window's band and a
tail's pad columns are all (`first`, `last`): one traced body for every
one of them.

One ``pallas_call`` named ``banded_flash``.  The grid is (K/V head,
query tile).  A step's queries are the ``G = H / n_kv_head`` query
heads of the group over one tile of columns.  q arrives TRANSPOSED,
(H * hd, T): a (G * hd, block_q) block is the group's heads, each
``hd`` rows of it the right-hand side of that head's score product as
it lies, and the transpose outside is a bitcast wherever the rotary's
fusion already writes its result columns-minor (Laguna's does: what was
a 0.4 ms copy a layer of q as (T, H * hd) at 8,192 columns is gone; my
chip run, PR 55).  K and V stay in HBM whole ((S, 1,024) bf16 at S =
8,704 is 17.8 MB): a step walks the key tiles ``lo[i] .. hi[i] - 1``
alone, ``min(first) // block_k`` to ``max(last) // block_k`` of its
columns as `attend_banded`'s loop bounds are, each tile a (block_k, hd)
lane slice of the folded rows copied where it lies into one of two
buffers while the tile before it is attended.  So a band costs its
width, a triangle its half, a tile of pad columns nothing.  Only a key
tile that some column of the step does not see whole builds a mask
(``flo[i] <= j < fhi[i]`` needs none).  The walk is a rolled
``fori_loop``: its length is data; the group's heads inside a tile are
unrolled, phase by phase (`BLOCK_Q`'s note has what a rolled loop over
them costs).  (A step's first tile started behind the last of the step
before it, so that no copy stands exposed, took 1 to 3% off a call and
is not kept: 3.30 -> 3.28 ms at Laguna's band of 8,192 columns, 1.57
-> 1.52 at Phi-4's of 4,096.)

Scores are held transposed, (keys, queries), as ops/mla_flash_prefill
and the triangle train kernels hold them: the softmax's row statistics
then lie along lanes.  Operands go to the MXU as stored (bf16); scores,
running maximum, sum and weighted sum are float32; probabilities are
cast to the values' dtype before the weighted sum: `attend_banded`'s
operations in its order, nothing approximated and no admitted key
skipped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes
from ray_tpu.ops.flash_attention import _NEG_INF, _dot

_LANES = 128
#: columns a grid step attends, and keys a tile of its walk: 256 of
#: both divide every bucket and view of the three cells (Phi-4's 4,864
#: slots are 19 x 256).  What the chip said, ms a call with q's
#: transpose outside it (~0.4 to 1 ms; my chip run, PR 55), with each
#: head's columns in two strips: 256 x 256 / 512 x 256 at Laguna's
#: triangle of 8,192 columns 7.65 / 7.53, at its band 3.28 / 3.71, at
#: Phi-4's band of 4,096 columns 1.52 / 1.72.  A head's columns whole, as
#: here, read 7.88, 3.24 and 1.58 and halve what the kernel's lowering
#: and compile cost a program (eight buckets of two layer kinds: 0.15 s
#: and 0.7 s a call where the strips took 0.3 and 1.3); the heads in a
#: rolled loop, two at a time, lower in a third of that again and take
#: 10.8, 4.0 and 1.74: not kept
BLOCK_Q = 256
BLOCK_K = 256


def fits(T: int, S: int, H: int, n_kv_head: int, hd: int, width: int,
         block_q: int = BLOCK_Q, block_k: int = BLOCK_K) -> bool:
    """Whether the kernel takes these shapes: heads of whole lanes,
    folded rows of exactly the K/V heads, whole tiles of queries and of
    keys."""
    return (hd == _LANES and width == n_kv_head * hd
            and H % n_kv_head == 0 and T > 0
            and T % block_q == 0 and S % block_k == 0)


def walk(first, last, S: int, block_q: int = BLOCK_Q,
         block_k: int = BLOCK_K, xp=np):
    """What each query tile walks, (4, nq) int32: the key tiles ``lo <=
    j < hi`` it visits, of which ``flo <= j < fhi`` every column sees
    whole.  `first`, `last` (T,) as `banded_flash` takes them; `xp` is
    numpy for the host's count, jax.numpy for the call's own table."""
    empty = last < first
    first = xp.where(empty, S, first).reshape(-1, block_q)
    last = xp.where(empty, -1, last).reshape(-1, block_q)
    lo = xp.minimum(first.min(axis=1), S) // block_k
    hi = (last.max(axis=1) + block_k) // block_k
    flo = -(-first.max(axis=1) // block_k)
    fhi = (last.min(axis=1) + 1) // block_k
    return xp.stack([lo, xp.maximum(hi, lo), flo, fhi]).astype(xp.int32)


def _kernel(walk_ref, q_ref, first_ref, last_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_scr, l_scr, acc_scr, *, scale: float):
    """One tile of columns of one K/V head's group.  Prefetched: the
    walk (4 * nq,), `walk`'s rows end to end.  q (G * hd, bq), o (bq,
    G * hd); first, last (1, bq) (an empty column's are S and -1);
    k_hbm, v_hbm (S, n_kv * hd) in HBM; scratch: two key and two value
    tiles (2, bk, hd), their DMA semaphores (2, 2), the running maximum
    and sum (1, G * bq) and the weighted sum (hd, G * bq), float32."""
    from jax.experimental.pallas import tpu as pltpu

    g, i, nq = pl.program_id(0), pl.program_id(1), pl.num_programs(1)
    bq = q_ref.shape[1]
    _, bk, hd = kbuf.shape
    G = q_ref.shape[0] // hd
    lo, hi = walk_ref[i], walk_ref[nq + i]
    flo, fhi = walk_ref[2 * nq + i], walk_ref[3 * nq + i]
    lanes = pl.ds(pl.multiple_of(g * hd, hd), hd)

    def copies(j, buf):
        at = pl.ds(pl.multiple_of(j * bk, bk), bk)
        return (pltpu.make_async_copy(k_hbm.at[at, lanes], kbuf.at[buf],
                                      sems.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at, lanes], vbuf.at[buf],
                                      sems.at[1, buf]))

    @pl.when(lo < hi)
    def _first_tile():
        for c in copies(lo, 0):
            c.start()

    m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    # a head of the group: its lanes of q, and its place among the
    # (G * bq) rows of the running state
    heads = [(slice(h * hd, (h + 1) * hd), slice(h * bq, (h + 1) * bq))
             for h in range(G)]

    def attend(j, buf, masked: bool):
        # phase by phase over the heads: every head's scores, then
        # every head's softmax, then every head's weighted sum, so
        # that the chip runs one head's products beside another's
        # softmax (ops/mla_flash_prefill.py's strips)
        k, v = kbuf[buf], vbuf[buf]
        scores = []
        for lanes_of, _ in heads:
            st = _dot(k, q_ref[lanes_of, :], 1, 0) * scale     # (bk, bq)
            ok = None
            if masked:
                at = j * bk + lax.broadcasted_iota(jnp.int32, st.shape, 0)
                ok = (at >= first_ref[...]) & (at <= last_ref[...])
                st = jnp.where(ok, st, _NEG_INF)
            scores.append((st, ok))
        soft = []
        for (_, rows), (st, ok) in zip(heads, scores):
            m = m_scr[:, rows]
            m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
            e = jnp.exp(st - m_new)
            if masked:
                # a column that has met no key yet has m_new == -1e30
                # and exp(0) == 1 on every masked key: zero them
                e = jnp.where(ok, e, 0.0)
            alpha = jnp.exp(m - m_new)
            m_scr[:, rows] = m_new
            l_scr[:, rows] = l_scr[:, rows] * alpha + jnp.sum(
                e, axis=0, keepdims=True)
            soft.append((alpha, e.astype(v.dtype)))
        for (_, rows), (alpha, e) in zip(heads, soft):
            acc_scr[:, rows] = acc_scr[:, rows] * alpha + _dot(v, e, 0, 0)

    def tile(j, carry):
        buf = (j - lo) % 2

        @pl.when(j + 1 < hi)
        def _next_tile():
            for c in copies(j + 1, 1 - buf):
                c.start()

        for c in copies(j, buf):
            c.wait()
        whole = (j >= flo) & (j < fhi)
        pl.when(whole)(functools.partial(attend, j, buf, False))
        pl.when(jnp.logical_not(whole))(
            functools.partial(attend, j, buf, True))
        return carry

    lax.fori_loop(lo, hi, tile, 0)
    out = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).T   # (G bq, hd)
    for h in range(G):
        o_ref[:, h * hd:(h + 1) * hd] = out[h * bq:(h + 1) * bq].astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "n_kv_head", "head_dim", "scale", "block_q", "block_k", "interpret"))
def banded_flash(q, k, v, first, last, *, n_kv_head: int, head_dim: int,
                 scale: float, block_q: int = BLOCK_Q,
                 block_k: int = BLOCK_K, interpret: bool = False):
    """q (T, H, hd); k, v (S, n_kv_head * hd) folded; first, last (T,)
    int32, traced or not -> (T, H, hd) in v's dtype (module docstring).
    ``interpret=True`` runs the kernel in the Pallas interpreter (the
    CPU tests)."""
    from jax.experimental.pallas import tpu as pltpu

    T, H, hd = q.shape
    S, width = k.shape
    if hd != head_dim or not fits(T, S, H, n_kv_head, hd, width, block_q,
                                  block_k):
        raise ValueError(
            f"{T} queries of {H} heads of {hd} over {S} rows of {width} "
            f"lanes ({n_kv_head} K/V heads) are not whole tiles of "
            f"{block_q} x {block_k}")
    G = H // n_kv_head
    dt = v.dtype
    i32 = jnp.int32
    first, last = first.astype(i32), last.astype(i32)
    empty = last < first
    reach = (jnp.where(empty, S, first)[None],
             jnp.where(empty, -1, last)[None])

    def column_rows():
        return pl.BlockSpec((1, block_q), lambda g, i, w: (0, i))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_kv_head, T // block_q),
            in_specs=[pl.BlockSpec((G * hd, block_q), lambda g, i, w: (g, i)),
                      column_rows(), column_rows(),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block_q, G * hd),
                                   lambda g, i, w: (i, g)),
            scratch_shapes=[pltpu.VMEM((2, block_k, hd), dt),
                            pltpu.VMEM((2, block_k, hd), dt),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((1, G * block_q), jnp.float32),
                            pltpu.VMEM((1, G * block_q), jnp.float32),
                            pltpu.VMEM((hd, G * block_q), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, H * hd), dt),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a key tile's scores, weights and their casts for all the
            # group's heads at once: ~6 MB at 256 x 256 and 8 heads
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
        name=scopes.BANDED_FLASH,
    )(walk(first, last, S, block_q, block_k, xp=jnp).reshape(-1),
      q.astype(dt).reshape(T, H * hd).T, *reach, k.astype(dt), v)
    return out.reshape(T, H, hd)


__all__ = ["BLOCK_Q", "BLOCK_K", "fits", "walk", "banded_flash"]
