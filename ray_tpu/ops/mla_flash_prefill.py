"""One sequence's causal prefill attention with latent attention's (MLA)
EXPANDED keys: a forward-only flash kernel whose keys are wider than
its values and whose rotary key is one for all heads.

A prompt tail of T columns (right-aligned: `pad` pad columns first)
stands behind `prefix_len` resident slots of the same sequence.  Query
column t of head h scores slot s with

  ``(q_nope[t, h] . k_nope[s, h] + q_rope[t, h] . k_rope[s]) * scale``

and sees the slots ``s <= prefix_len + t - pad``; a pad column sees
none and returns zeros: `models/kimi_k2_decode.attend_blockwise`'s
``reach``, and that function is what the kernel is held to
(tests/test_mla_flash_prefill.py).  The rotary key is never broadcast
to the heads nor concatenated to a 192-wide key: the score is the sum
of two products.

`mla_flash_prefill` is one ``pallas_call`` named ``mla_flash_prefill``.
The grid is (head, pair): the pairs are the (query tile, key tile)
pairs the diagonal leaves, query-major, listed once a call from the two
prefetched scalars (`walk`); a key tile above a query tile's diagonal is
no grid step at all, and only the tiles the diagonal runs through build
a mask.  K and V stream a tile a step through the grid's own double
buffer (a head's keys at 8,704 slots do not stay in VMEM); the running
maximum, sum and weighted sum are float32 VMEM scratch, flushed once a
query tile.  Operands go to the MXU as stored (bf16); scores, softmax
state and accumulation are float32; probabilities are cast to the
values' dtype before the weighted sum.  One traced body for every
shape: the walk's length is data.

Scores are held transposed, (keys, queries), as the triangle train
kernels hold them (ops/flash_attention.py): the softmax's row
statistics then lie along lanes.  The grid has room for the longest
walk a shape can ask for (`prefix_len + T - pad <= S`); the steps a
shorter walk leaves over repeat the last pair's blocks (no copy) and do
nothing.
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

#: queries and keys a grid step attends.  What the chip said at T =
#: 8,192 (64 heads, S = 8,704, one call, a tile one strip; my chip run,
#: PR 36): 1,024 x 512 15.2 ms, 512 x 512 16.1, 2,048 x 512 15.2,
#: 512 x 256 19.9, 256 x 512 20.0, 1,024 x 256 17.0; 8,704 slots are
#: 17 x 512, so no wider key tile divides them
BLOCK_Q = 1024
BLOCK_K = 512
#: queries a strip of a tile, what the phases of `_kernel` interleave:
#: 1,024 x 512 in strips of 1,024 / 512 / 256 takes 15.2 / 14.3 / 12.9
#: ms, 2,048 x 512 in strips of 512: 14.7
STRIP = 256


def fits(T: int, S: int, block_q: int = BLOCK_Q,
         block_k: int = BLOCK_K) -> bool:
    """Whether whole tiles cover T queries over S slots."""
    return T % block_q == 0 and S % block_k == 0 and S >= T > 0


def _room(T: int, S: int, bq: int, bk: int) -> np.ndarray:
    """Key tiles query tile i can need at most (nq,): its last column
    reaches slot ``S - T + (i + 1) * bq - 1`` when the tail ends the
    view."""
    last = S - T + (np.arange(T // bq) + 1) * bq
    return np.minimum(S // bk, -(-last // bk))


def walk(T: int, S: int, prefix_len, pad, block_q: int = BLOCK_Q,
         block_k: int = BLOCK_K, xp=np):
    """Key tiles each query tile visits (nq,): up to the tile its last
    column's reach lies in; a tile of pad columns visits one (and
    returns zeros).  `xp` is numpy for the host's count, jax.numpy for
    the call's own tables."""
    last = (xp.arange(T // block_q) + 1) * block_q - 1
    top = xp.where(last >= pad, prefix_len - pad + last, 0)
    return xp.clip(top // block_k + 1, 1, _room(T, S, block_q, block_k))


def _kernel(diag_ref, qi_ref, kj_ref, qn_ref, qr_ref, kn_ref, kr_ref,
            vt_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float,
            strip: int):
    """One (query tile, key tile) pair of one head.  Prefetched
    scalars: (prefix_len, pad, pairs walked); the pairs' query tiles
    (P + 1,) and key tiles (P + 1,).  qn (bq, nope), qr (bq, rope), kn
    (bk, nope), kr (bk, rope), vt (v, bk) -> o (bq, v); scratch: the
    running maximum and sum (1, bq) and the weighted sum (v, bq),
    float32."""
    p = pl.program_id(1)
    bq, bk = qn_ref.shape[0], kn_ref.shape[0]
    prefix, pad, pairs = diag_ref[0], diag_ref[1], diag_ref[2]
    qi, kj = qi_ref[p], kj_ref[p]
    live = p < pairs
    # the least reach of the tile's real columns: a key tile that ends
    # at or under it is seen whole by every one of them
    low = prefix - pad + jnp.maximum(qi * bq, pad)
    crossed = (kj + 1) * bk - 1 > low

    @pl.when(live & (kj == 0))
    def _open():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def attend(masked: bool):
        # in strips of `strip` queries, phase by phase: every strip's
        # scores, then every strip's softmax, then every strip's
        # weighted sum, state read before and written after.  A strip's
        # products need no other strip's softmax, so the chip runs the
        # one beside the other; one strip a tile leaves the MXU idle
        # through the softmax and the VPU idle through the products
        # (15.2 ms a layer at T = 8,192 where this takes 12.9)
        kn, kr, vt = kn_ref[...], kr_ref[...], vt_ref[...]
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        strips = [slice(s0, s0 + strip) for s0 in range(0, bq, strip)]
        scores = []
        for qs in strips:
            st = (_dot(kn, qn_ref[qs, :], 1, 1)
                  + _dot(kr, qr_ref[qs, :], 1, 1)) * scale   # (bk, strip)
            if masked:
                # slot kj * bk + k <= prefix + (qi * bq + q) - pad
                ahead = (lax.broadcasted_iota(jnp.int32, st.shape, 0)
                         - lax.broadcasted_iota(jnp.int32, st.shape, 1))
                st = jnp.where(
                    ahead <= prefix - pad + qi * bq + qs.start - kj * bk,
                    st, _NEG_INF)
            scores.append(st)
        soft = []
        for qs, st in zip(strips, scores):
            m_new = jnp.maximum(m[:, qs], jnp.max(st, axis=0, keepdims=True))
            # a real column has met slot 0 in its first tile, so under
            # a finite maximum a masked key's exp is 0; a pad column's
            # state is whatever comes and is zeroed at the flush
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m[:, qs] - m_new)
            soft.append((m_new, l[:, qs] * alpha + jnp.sum(
                pt, axis=0, keepdims=True), alpha, pt.astype(vt.dtype)))
        sums = [acc[:, qs] * alpha + _dot(vt, pt, 1, 0)
                for qs, (_, _, alpha, pt) in zip(strips, soft)]
        for qs, (m_new, l_new, _, _), acc_new in zip(strips, soft, sums):
            m_scr[:, qs] = m_new
            l_scr[:, qs] = l_new
            acc_scr[:, qs] = acc_new

    pl.when(live & crossed)(functools.partial(attend, True))
    pl.when(live & jnp.logical_not(crossed))(
        functools.partial(attend, False))

    @pl.when(live & ((p + 1 == pairs) | (qi_ref[p + 1] != qi)))
    def _flush():
        col = qi * bq + lax.broadcasted_iota(jnp.int32, l_scr.shape, 1)
        out = jnp.where(col >= pad,
                        acc_scr[...] / jnp.maximum(l_scr[...], 1e-30), 0.0)
        o_ref[...] = out.T.astype(o_ref.dtype)


def _call(qn, qr, kn, kr, vt, prefix_len, pad, *, scale, bq, bk, strip,
          interpret):
    """The kernel over operands as it reads them: qn (H, T, nope), qr
    (H, T, rope), kn (H, S, nope), kr (S, rope), vt (H, v, S) ->
    (T, H * v)."""
    from jax.experimental.pallas import tpu as pltpu

    H, T, n = qn.shape
    S, r = kr.shape
    dv = vt.shape[1]
    i32 = jnp.int32
    prefix_len, pad = jnp.asarray(prefix_len, i32), jnp.asarray(pad, i32)
    # the pairs, query-major; past the walk's end the last pair again
    steps = int(_room(T, S, bq, bk).sum())
    visits = walk(T, S, prefix_len, pad, bq, bk, xp=jnp).astype(i32)
    ends = jnp.cumsum(visits)
    at = jnp.minimum(jnp.arange(steps + 1, dtype=i32), ends[-1] - 1)
    qi_of = jnp.sum(at[:, None] >= ends[None, :], axis=1, dtype=i32)
    kj_of = at - (ends - visits)[qi_of]

    def q_tile(width):
        return pl.BlockSpec((None, bq, width),
                            lambda h, p, d, qi, kj: (h, qi[p], 0))

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, strip=strip),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(H, steps),
            in_specs=[
                q_tile(n), q_tile(r),
                pl.BlockSpec((None, bk, kn.shape[-1]),
                             lambda h, p, d, qi, kj: (h, kj[p], 0)),
                pl.BlockSpec((bk, r), lambda h, p, d, qi, kj: (kj[p], 0)),
                pl.BlockSpec((None, dv, bk),
                             lambda h, p, d, qi, kj: (h, 0, kj[p]))],
            out_specs=pl.BlockSpec((bq, dv),
                                   lambda h, p, d, qi, kj: (qi[p], h)),
            scratch_shapes=[pltpu.VMEM((1, bq), jnp.float32),
                            pltpu.VMEM((1, bq), jnp.float32),
                            pltpu.VMEM((dv, bq), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((T, H * dv), vt.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name=scopes.MLA_FLASH_PREFILL,
    )(jnp.stack([prefix_len, pad, ends[-1]]), qi_of, kj_of, qn, qr, kn, kr,
      vt)


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "strip", "interpret"))
def mla_flash_prefill(q, k_nope, k_rope, v, prefix_len, pad, *,
                      scale: float, block_q: int = BLOCK_Q,
                      block_k: int = BLOCK_K, strip: int = STRIP,
                      interpret: bool = False):
    """q (T, H, nope + rope) the tail's queries, `pad` pad columns
    first; k_nope (S, H, nope), k_rope (S, rope), v (S, H, v) the
    sequence's slots up-projected, the tail's own among them at
    ``prefix_len ..``; -> (T, H, v) in v's dtype.  `prefix_len` and
    `pad` may be traced.  ``interpret=True`` runs the kernel in the
    Pallas interpreter (the CPU tests)."""
    T, H, _ = q.shape
    S, _, n = k_nope.shape
    if not fits(T, S, block_q, block_k) or block_q % strip:
        raise ValueError(f"{T} queries over {S} slots are not whole "
                         f"tiles of {block_q} x {block_k} in strips of "
                         f"{strip}")
    dt = v.dtype
    q = q.astype(dt)
    # a head's tiles as the kernel reads them (the products that make
    # K and V write these orders themselves)
    out = _call(q[..., :n].transpose(1, 0, 2), q[..., n:].transpose(1, 0, 2),
                k_nope.astype(dt).transpose(1, 0, 2), k_rope.astype(dt),
                v.transpose(1, 2, 0), prefix_len, pad, scale=scale,
                bq=block_q, bk=block_k, strip=strip, interpret=interpret)
    return out.reshape(T, H, -1)


__all__ = ["BLOCK_Q", "BLOCK_K", "STRIP", "fits", "walk",
           "mla_flash_prefill"]
