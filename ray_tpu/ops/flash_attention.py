"""Flash attention for TPU, written in pallas.

Blockwise online-softmax attention (the FlashAttention recurrence): the
T×T score matrix never materializes in HBM.  Both matmuls hit the MXU
with float32 accumulation; scores and exp are float32.  Backward is the
recompute scheme: forward saves only O(T) row statistics (logsumexp)
beside q, k, v and o, and the backward recomputes score tiles on the
fly, so backward memory is O(T) as well.

Three families of kernels share that mathematics (flash_attention picks
one from what the call can see: causal, T, D, dtype, explicit blocks):

* **triangle** (`flash_tri_fwd`, `flash_tri_bwd`): the default for
  causal T <= 2048.  One grid step a (batch*head); the head stays whole
  in VMEM and the kernel walks, unrolled, only the tiles on or under the
  diagonal (`causal_walk`: 3 of 4 forward tiles of 512 and 10 of 16
  backward tiles of 256 at T=1024); only the tiles ON the diagonal build
  a mask.  The backward is one pass (S, exp and dP once a tile).
* **classic** (`flash_fwd`, `flash_dq`, `flash_dkv`): the kv loop is a
  grid dimension — pallas double-buffers the k/v block DMAs against
  compute — and the online-softmax state (m, l, acc) lives in VMEM
  scratch across the kv grid steps.  Non-causal attention, explicit
  `block_*` arguments and sequence lengths the triangle's tile does not
  divide run here.  Its causal form skips a tile wholly above the
  diagonal (`pl.when`), but at the blocks `auto_blocks` picks for
  T <= 2048 (one whole-T key tile) there is no such tile: it computes
  the whole square and masks half of it away, 18 half-squares of
  matmul for the 7 needed.
* **resident-kv** (`flash_res_*`): k/v whole in VMEM, the kv loop inside
  the kernel with a trip count that stops at the diagonal.  The path of
  causal T > 2048, where neither of the others compiles.

No analog in the reference framework (it defers attention to torch); the
algorithm is from the public FlashAttention/blockwise-attention literature
(see PAPERS.md), implemented fresh against the pallas TPU API
(/opt/skills/guides/pallas_guide.md).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

# Classic kernels: whole-1024 tiles are the fastest GRID tiling on v5e
# at GPT-2 shapes (T=1024, D=64): one tile per (batch*head) avoids the
# online-softmax scratch revisit and still fits VMEM (4 MiB f32 score
# tile) — at the price of computing the masked half (the triangle
# kernels below are what causal T <= 2048 takes by default).  _blocks()
# caps these to T, and longer sequences fall back to multi-tile
# streaming.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30


def _blocks(T: int, want: int) -> int:
    b = min(want, T)
    while T % b:
        b //= 2
    return max(b, 1)


def _causal_tile_visible(qi, ki, block_q: int, block_k: int):
    """True unless the (qi, ki) tile is entirely above the diagonal."""
    return qi * block_q + block_q - 1 >= ki * block_k


def _tile_mask(qi, ki, block_q: int, block_k: int):
    rows = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    cols = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    return rows >= cols


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale: float, block_q: int, block_k: int, causal: bool,
                num_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    visible = _causal_tile_visible(qi, ki, block_q, block_k) \
        if causal else True

    @pl.when(visible)
    def _tile():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_tile_mask(qi, ki, block_q, block_k), s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ki == num_kv - 1)
    def _flush():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[:] = (acc_scr[:] / l).astype(o_ref.dtype)
        lse_ref[0, :] = (m_scr[:] + jnp.log(l))[:, 0]


def _fwd(q3, k3, v3, *, scale, block_q, block_k, causal, interpret):
    """q3/k3/v3: (BH, T, D) → o (BH, T, D), lse (BH, 1, T) float32."""
    BH, T, D = q3.shape
    bq = _blocks(T, block_q)
    bk = _blocks(T, block_k)
    nq, nk = T // bq, T // bk
    kern = functools.partial(_fwd_kernel, scale=scale, block_q=bq,
                             block_k=bk, causal=causal, num_kv=nk)
    o, lse = pl.pallas_call(
        kern,
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[_vmem((bq, 1)), _vmem((bq, 1)), _vmem((bq, D))],
        interpret=interpret,
        name=scopes.FLASH_FWD,
    )(q3, k3, v3)
    return o, lse


def _delta(do3, o3):
    """rowsum(dO * O), the softmax backward's correction: (BH, 1, T)."""
    return jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                   axis=-1)[:, None, :]


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale: float, block_q: int, block_k: int,
                   causal: bool, num_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    visible = _causal_tile_visible(qi, ki, block_q, block_k) \
        if causal else True

    @pl.when(visible)
    def _tile():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_tile_mask(qi, ki, block_q, block_k), s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_scr[:] = dq_scr[:] + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv - 1)
    def _flush():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    block_q: int, block_k: int, causal: bool, num_q: int):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    visible = _causal_tile_visible(qi, ki, block_q, block_k) \
        if causal else True

    @pl.when(visible)
    def _tile():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        do = do_ref[:]
        lse = lse_ref[0, :][:, None]
        delta = delta_ref[0, :][:, None]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            s = jnp.where(_tile_mask(qi, ki, block_q, block_k), s, _NEG_INF)
        p = jnp.exp(s - lse)                       # (bq, bk)
        dv_scr[:] = dv_scr[:] + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale              # (bq, bk)
        dk_scr[:] = dk_scr[:] + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _flush():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(res, do3, *, scale, block_q, block_k, causal, interpret):
    q3, k3, v3, o3, lse = res
    BH, T, D = q3.shape
    bq = _blocks(T, block_q)
    bk = _blocks(T, block_k)
    nq, nk = T // bq, T // bk
    delta = _delta(do3, o3)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, block_q=bq,
                          block_k=bk, causal=causal, num_kv=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((None, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda bh, qi, ki:
                               (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
        scratch_shapes=[_vmem((bq, D))],
        interpret=interpret,
        name=scopes.FLASH_DQ,
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, block_q=bq,
                          block_k=bk, causal=causal, num_q=nq),
        grid=(BH, nk, nq),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, bq, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, ki, qi: (bh, 0, qi)),
            pl.BlockSpec((None, 1, bq), lambda bh, ki, qi: (bh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v3.dtype),
        ],
        scratch_shapes=[_vmem((bk, D)), _vmem((bk, D))],
        interpret=interpret,
        name=scopes.FLASH_DKV,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Resident-kv kernels: k/v live whole-T in VMEM and the kv loop runs
# INSIDE the kernel as a lax.fori_loop whose trip count depends on the
# q-tile index.  This gets causal work-skipping (only ~(qi+1)/nq of the
# score matrix is computed per q tile) without making kv a grid
# dimension — the online-softmax scratch revisit across kv grid steps is
# a measured ~10x cliff on this toolchain (ROADMAP.md A2).  k+v at
# bf16 T=4096 is 1 MiB of VMEM, so residency also unlocks long
# single-chip sequences that the whole-T score tile cannot compile.
# ---------------------------------------------------------------------------

def _fwd_res_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                    bq: int, chunk: int, causal: bool, T: int):
    qi = pl.program_id(1)
    D = q_ref.shape[-1]
    q = q_ref[:]                                   # (bq, D)
    nchunks = T // chunk
    if causal:
        nvis = jnp.minimum((qi * bq + bq + chunk - 1) // chunk, nchunks)
    else:
        nvis = nchunks
    rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, chunk), 0)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(i * chunk, chunk), :]
        v = v_ref[pl.ds(i * chunk, chunk), :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            cols = i * chunk + lax.broadcasted_iota(jnp.int32, (bq, chunk),
                                                    1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return m_new, l, alpha * acc + pv

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, D), jnp.float32)
    m, l, acc = lax.fori_loop(0, nvis, body, (m0, l0, a0))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    lse_ref[0, :] = (m + jnp.log(l))[:, 0]


def _fwd_res(q3, k3, v3, *, scale, bq, chunk, causal, interpret):
    BH, T, D = q3.shape
    nq = T // bq
    kern = functools.partial(_fwd_res_kernel, scale=scale, bq=bq,
                             chunk=chunk, causal=causal, T=T)
    return pl.pallas_call(
        kern,
        grid=(BH, nq),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, T, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, T, D), lambda bh, qi: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        interpret=interpret,
        name=scopes.FLASH_RES_FWD,
    )(q3, k3, v3)


def _bwd_dq_res_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dq_ref, *, scale: float, bq: int, chunk: int,
                       causal: bool, T: int):
    qi = pl.program_id(1)
    D = q_ref.shape[-1]
    q = q_ref[:]
    do = do_ref[:]
    lse = lse_ref[0, :][:, None]
    delta = delta_ref[0, :][:, None]
    nchunks = T // chunk
    if causal:
        nvis = jnp.minimum((qi * bq + bq + chunk - 1) // chunk, nchunks)
    else:
        nvis = nchunks
    rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, chunk), 0)

    def body(i, dq):
        k = k_ref[pl.ds(i * chunk, chunk), :]
        v = v_ref[pl.ds(i * chunk, chunk), :]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            cols = i * chunk + lax.broadcasted_iota(jnp.int32, (bq, chunk),
                                                    1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + lax.dot_general(ds.astype(k.dtype), k,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    dq = lax.fori_loop(0, nvis, body, jnp.zeros((bq, D), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _bwd_dkv_res_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dk_ref, dv_ref, *, scale: float, bk: int,
                        chunk: int, causal: bool, T: int):
    ki = pl.program_id(1)
    D = k_ref.shape[-1]
    k = k_ref[:]                                   # (bk, D)
    v = v_ref[:]
    nchunks = T // chunk
    start = (ki * bk) // chunk if causal else 0
    cols = ki * bk + lax.broadcasted_iota(jnp.int32, (chunk, bk), 1)

    def body(j, carry):
        dk, dv = carry
        qj = q_ref[pl.ds(j * chunk, chunk), :]
        doj = do_ref[pl.ds(j * chunk, chunk), :]
        lse = lse_ref[0, pl.ds(j * chunk, chunk)][:, None]
        delta = delta_ref[0, pl.ds(j * chunk, chunk)][:, None]
        s = lax.dot_general(qj, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = j * chunk + lax.broadcasted_iota(jnp.int32, (chunk, bk),
                                                    0)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                       # (chunk, bk)
        dv = dv + lax.dot_general(p.astype(doj.dtype), doj,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dp = lax.dot_general(doj, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk = dk + lax.dot_general(ds.astype(qj.dtype), qj,
                                  (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, D), jnp.float32)
    dk, dv = lax.fori_loop(start, nchunks, body, (z, z))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_res(res, do3, *, scale, bq, bk, chunk, causal, interpret):
    q3, k3, v3, o3, lse = res
    BH, T, D = q3.shape
    delta = _delta(do3, o3)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_res_kernel, scale=scale, bq=bq,
                          chunk=chunk, causal=causal, T=T),
        grid=(BH, T // bq),
        in_specs=[
            pl.BlockSpec((None, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, T, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, T, D), lambda bh, qi: (bh, 0, 0)),
            pl.BlockSpec((None, bq, D), lambda bh, qi: (bh, qi, 0)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi: (bh, 0, qi)),
            pl.BlockSpec((None, 1, bq), lambda bh, qi: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((None, bq, D), lambda bh, qi: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
        interpret=interpret,
        name=scopes.FLASH_RES_DQ,
    )(q3, k3, v3, do3, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_res_kernel, scale=scale, bk=bk,
                          chunk=chunk, causal=causal, T=T),
        grid=(BH, T // bk),
        in_specs=[
            pl.BlockSpec((None, T, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((None, T, D), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((None, 1, T), lambda bh, ki: (bh, 0, 0)),
            pl.BlockSpec((None, 1, T), lambda bh, ki: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, D), lambda bh, ki: (bh, ki, 0)),
            pl.BlockSpec((None, bk, D), lambda bh, ki: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, T, D), v3.dtype),
        ],
        interpret=interpret,
        name=scopes.FLASH_RES_DKV,
    )(q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_res(q3, k3, v3, scale, bq, bk, chunk, causal, interpret):
    o, _ = _fwd_res(q3, k3, v3, scale=scale, bq=bq, chunk=chunk,
                    causal=causal, interpret=interpret)
    return o


def _flash_res_fwd(q3, k3, v3, scale, bq, bk, chunk, causal, interpret):
    o, lse = _fwd_res(q3, k3, v3, scale=scale, bq=bq, chunk=chunk,
                      causal=causal, interpret=interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_res_bwd(scale, bq, bk, chunk, causal, interpret, res, do3):
    return _bwd_res(res, do3, scale=scale, bq=bq, bk=bk, chunk=chunk,
                    causal=causal, interpret=interpret)


_flash_res.defvjp(_flash_res_fwd, _flash_res_bwd)


# ---------------------------------------------------------------------------
# Triangle kernels: one grid step per (batch*head); q, k, v (and dO) of
# the head stay whole in VMEM and the kernel walks, unrolled over static
# slices, only the (q tile, k tile) pairs on or under the diagonal.
# Pairs strictly under it build no mask (no iota, compare or select);
# the pairs the diagonal crosses share one.  A pair above the diagonal
# contributes exactly zero in the kernels above (exp(-1e30 - m) == 0.0
# in float32), so leaving it out changes no sum.
#
# Scores are held TRANSPOSED, (k tile, q tile): a query's running max,
# sum, log-sum-exp and delta then lie along lanes, as the forward saves
# them ((1, T) float32), two vregs a 256-query tile.  As a (bq, 1)
# column they are bq/8 vregs with one lane of 128 in use, and the
# rescale's exp(m - m_new) alone costs half as many vreg exps as the
# 256-wide score tile it belongs to.  The only transposes left are of
# (tile, D) operands and results, never of a score tile.
# ---------------------------------------------------------------------------

def causal_walk(T: int, bq: int, bk: int):
    """The (q tile, k tile) pairs a causal kernel has to visit, as
    ``(qi, ki, crossed)`` in q-major order: every pair that holds a key
    at or before one of its queries; ``crossed`` says the diagonal runs
    through the pair, so it needs the mask.  What the triangle kernels'
    loops are built from."""
    walk = []
    for qi in range(T // bq):
        for ki in range(T // bk):
            if ki * bk > qi * bq + bq - 1:
                continue                # wholly above the diagonal
            walk.append((qi, ki, (ki + 1) * bk - 1 > qi * bq))
    return walk


def causal_tiles(T: int, bq: int, bk: int):
    """(visited, all) tile pairs of a causal walk: n(n+1)/2 of n*n for
    square tiles (10 of 16 at T=1024 in 256s, 36 of 64 in 128s)."""
    return len(causal_walk(T, bq, bk)), (T // bq) * (T // bk)


def _walk_by(T: int, t: int, outer: int):
    """causal_walk in square tiles of t, grouped by its q tile (outer=0)
    or k tile (outer=1): {outer tile: [(inner tile, crossed), ...]}."""
    groups = {}
    for pair in causal_walk(T, t, t):
        groups.setdefault(pair[outer], []).append(
            (pair[1 - outer], pair[2]))
    return groups


def _dot(a, b, ca: int, cb: int):
    return lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _diagonal_mask(t: int):
    """`query >= key` on a transposed (keys, queries) diagonal tile: in
    a walk of square tiles the crossed pairs are the diagonal ones, and
    they all share this mask."""
    return (lax.broadcasted_iota(jnp.int32, (t, t), 1)
            >= lax.broadcasted_iota(jnp.int32, (t, t), 0))


def _tri_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale: float,
                    t: int, T: int):
    mask = _diagonal_mask(t)
    vts = {}
    for qi, tiles in _walk_by(T, t, 0).items():
        qs = pl.ds(qi * t, t)
        q = q_ref[qs, :]
        m = l = acc = None              # (1, t), (1, t), (D, t)
        for ki, crossed in tiles:
            ks = pl.ds(ki * t, t)
            if ki not in vts:
                vts[ki] = v_ref[ks, :].T                    # (D, t)
            vt = vts[ki]
            st = _dot(k_ref[ks, :], q, 1, 1) * scale        # (keys, queries)
            if crossed:
                st = jnp.where(mask, st, _NEG_INF)
            m_tile = jnp.max(st, axis=0, keepdims=True)
            if m is None:
                # first tile of the row: the classic kernel's rescale of
                # an all-zero state by exp(-1e30 - m) == 0.0, left out
                m = m_tile
                pt = jnp.exp(st - m)
                l = jnp.sum(pt, axis=0, keepdims=True)
                acc = _dot(vt, pt.astype(vt.dtype), 1, 0)
                continue
            m_new = jnp.maximum(m, m_tile)
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(pt, axis=0, keepdims=True)
            acc = acc * alpha + _dot(vt, pt.astype(vt.dtype), 1, 0)
            m = m_new
        l = jnp.maximum(l, 1e-30)
        o_ref[qs, :] = (acc / l).T.astype(o_ref.dtype)
        lse_ref[:, qs] = m + jnp.log(l)


def _tri_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dq_ref, dk_ref, dv_ref, dqt_scr, *, scale: float,
                    t: int, T: int):
    # one pass: S, exp and dP once a pair (five matmuls, not the seven of
    # a dq and a dk/dv kernel).  dK and dV are carried over the q tiles of
    # a k tile; dQ is summed transposed, dQ^T = K^T dS^T in a (D, T)
    # float32 scratch, so that the small operand (K, once a k tile) is
    # the one transposed and not the score tile
    mask = _diagonal_mask(t)
    for ki, tiles in _walk_by(T, t, 1).items():
        ks = pl.ds(ki * t, t)
        k = k_ref[ks, :]
        kt = k.T                                            # (D, t)
        v = v_ref[ks, :]
        dk = dv = None
        for qi, crossed in tiles:
            qs = pl.ds(qi * t, t)
            q = q_ref[qs, :]
            do = do_ref[qs, :]
            st = _dot(k, q, 1, 1) * scale                   # (keys, queries)
            if crossed:
                st = jnp.where(mask, st, _NEG_INF)
            pt = jnp.exp(st - lse_ref[:, qs])
            dv_part = _dot(pt.astype(do.dtype), do, 1, 0)
            dst = (pt * (_dot(v, do, 1, 1) - delta_ref[:, qs]) * scale
                   ).astype(q.dtype)
            dk_part = _dot(dst, q, 1, 0)
            dqt_part = _dot(kt, dst, 1, 0)                  # (D, queries)
            if ki == 0:                 # k tile 0 is under every q tile
                dqt_scr[:, qs] = dqt_part
            else:
                dqt_scr[:, qs] = dqt_scr[:, qs] + dqt_part
            dv = dv_part if dv is None else dv + dv_part
            dk = dk_part if dk is None else dk + dk_part
        dk_ref[ks, :] = dk.astype(dk_ref.dtype)
        dv_ref[ks, :] = dv.astype(dv_ref.dtype)
    for qi in range(T // t):
        qs = pl.ds(qi * t, t)
        dq_ref[qs, :] = dqt_scr[:, qs].T.astype(dq_ref.dtype)


def _tri_call(kernel, name, heads, rows=(), *, out_heads: int,
              out_rows: int = 0, scratch=(), interpret):
    """One grid step a (batch*head): `heads` are (BH, T, D) operands,
    `rows` (BH, 1, T) float32 row statistics; the results are
    `out_heads` of the first kind, then `out_rows` of the second."""
    from jax.experimental.pallas import tpu as pltpu
    BH, T, D = heads[0].shape
    head = pl.BlockSpec((None, T, D), lambda bh: (bh, 0, 0))
    row = pl.BlockSpec((None, 1, T), lambda bh: (bh, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=[head] * len(heads) + [row] * len(rows),
        out_specs=[head] * out_heads + [row] * out_rows,
        out_shape=[jax.ShapeDtypeStruct((BH, T, D), heads[0].dtype)
                   ] * out_heads
        + [jax.ShapeDtypeStruct((BH, 1, T), jnp.float32)] * out_rows,
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=name,
    )(*heads, *rows)


def _tri_fwd(q3, k3, v3, *, scale, t, interpret):
    T = q3.shape[1]
    return _tri_call(
        functools.partial(_tri_fwd_kernel, scale=scale, t=t, T=T),
        scopes.FLASH_TRI_FWD, (q3, k3, v3), out_heads=1, out_rows=1,
        interpret=interpret)


def _tri_bwd(res, do3, *, scale, t, interpret):
    q3, k3, v3, o3, lse = res
    _, T, D = q3.shape
    return _tri_call(
        functools.partial(_tri_bwd_kernel, scale=scale, t=t, T=T),
        scopes.FLASH_TRI_BWD, (q3, k3, v3, do3), (lse, _delta(do3, o3)),
        out_heads=3, scratch=[_vmem((D, T))], interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_tri(q3, k3, v3, scale, t_fwd, t_bwd, interpret):
    o, _ = _tri_fwd(q3, k3, v3, scale=scale, t=t_fwd, interpret=interpret)
    return o


def _tri_vjp_fwd(q3, k3, v3, scale, t_fwd, t_bwd, interpret):
    o, lse = _tri_fwd(q3, k3, v3, scale=scale, t=t_fwd,
                      interpret=interpret)
    return o, (q3, k3, v3, o, lse)


def _tri_vjp_bwd(scale, t_fwd, t_bwd, interpret, res, do3):
    return _tri_bwd(res, do3, scale=scale, t=t_bwd, interpret=interpret)


_flash_tri.defvjp(_tri_vjp_fwd, _tri_vjp_bwd)


#: square tiles of the triangle walk, by measurement on a v5e at
#: (288, 1024, 64) bf16 (PERF.md, PR 29): the forward's 3 of 4 tiles of
#: 512 beat 10 of 16 of 256 (1.27 against 1.74 ms: a pair's fixed work
#: outweighs the eighth of the square it saves); the backward's five
#: matmuls a pair want 256 (2.59 against 2.68 ms at 512, 3.28 at 128)
TRI_TILE_FWD = 512
TRI_TILE_BWD = 256
#: the triangle kernels hold a head whole in VMEM; the chip's compiler
#: (described v5e, 16 MiB of scoped VMEM) takes T=2048 at D=128 in
#: float32 and refuses D=256 there
TRI_MAX_T = 2048
_TRI_MAX_HEAD_BYTES = 2048 * 128 * 4


def _triangle_plan(T: int, D: int, dtype, causal: bool):
    """(forward tile, backward tile) when auto dispatch takes the
    triangle kernels, else None: decided from what the call can see
    (causal, T, D, dtype) and nothing else."""
    if not causal or T > TRI_MAX_T or T % TRI_TILE_BWD:
        return None                     # classic kernels shrink blocks
    lanes = -(-D // 128) * 128          # VMEM pads the minor dim
    if T * lanes * jnp.dtype(dtype).itemsize > _TRI_MAX_HEAD_BYTES:
        return None
    return (TRI_TILE_FWD if T % TRI_TILE_FWD == 0 else TRI_TILE_BWD,
            TRI_TILE_BWD)


RESIDENT_BLOCK_Q = 256
RESIDENT_CHUNK = 512


def resolve_resident_mode(mode: str = "auto"):
    """Per-config resident-kv knob → the flash_attention ``resident_kv``
    tri-state (True/False/None=auto).  The RAYTPU_FLASH_RESIDENT env var
    is kept as a process-wide OVERRIDE ("1" forces on, "0" forces off)
    so the historical whole-process A/B workflow still works, but the
    primary switch is now per-config (``GPT2Config.flash_resident``) so
    sweep_tpu.py can A/B resident kernels per VARIANT."""
    import os

    env = os.environ.get("RAYTPU_FLASH_RESIDENT")
    if env == "1":
        return True
    if env == "0":
        return False
    if mode == "on":
        return True
    if mode == "off":
        return False
    return None


def _resident_plan(T: int, causal: bool):
    """The resident-kv configuration auto dispatch takes for seq length
    T, or None.  Measured v5e policy (PERF.md §6, PR 29: the A/B in the
    full 124M train step, 8 steps x 12 layers traced): at T=1024 the
    resident kernels LOSE to the classic whole-T tile, 1.78 / 1.69 /
    2.90 ms a layer (forward / dQ / dK+dV) against 1.23 / 1.51 / 2.48,
    step 268.7 against 254.9 ms, and lose more with a finer chunk (256:
    284.3 ms; 128: 315.5 ms): what their early stop skips (6 of 8
    tiles) costs less than their (bq, 1) softmax state and dynamic loop
    add.  Both lose to the triangle kernels (215.1 ms), which is what
    causal T <= 2048 takes.  Past T=2048 a head no longer stays whole
    in VMEM for those and the classic whole-T score tile no longer
    compiles (scoped-vmem OOM at (1024, 4096)): resident kv is what
    makes long single-chip sequences viable at all, and stays auto
    there.  Opt in below that per-config (flash_resident="on") or
    per-process (RAYTPU_FLASH_RESIDENT=1, resolved by
    resolve_resident_mode into an explicit resident_kv=True).
    Returns (bq, bk, chunk) or None."""
    if not causal:
        return None                 # no skip to win; classic path
    if T % RESIDENT_CHUNK or T % RESIDENT_BLOCK_Q:
        return None
    if T <= 2048:
        return None                 # measured slower (see above)
    return RESIDENT_BLOCK_Q, RESIDENT_BLOCK_Q, RESIDENT_CHUNK


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q3, k3, v3, scale, block_q, block_k, causal, interpret,
           block_q_bwd, block_k_bwd):
    o, _ = _fwd(q3, k3, v3, scale=scale, block_q=block_q, block_k=block_k,
                causal=causal, interpret=interpret)
    return o


def _flash_fwd(q3, k3, v3, scale, block_q, block_k, causal, interpret,
               block_q_bwd, block_k_bwd):
    o, lse = _fwd(q3, k3, v3, scale=scale, block_q=block_q, block_k=block_k,
                  causal=causal, interpret=interpret)
    return o, (q3, k3, v3, o, lse)


def _flash_bwd(scale, block_q, block_k, causal, interpret, block_q_bwd,
               block_k_bwd, res, do3):
    return _bwd(res, do3, scale=scale, block_q=block_q_bwd or block_q,
                block_k=block_k_bwd or block_k, causal=causal,
                interpret=interpret)


_flash.defvjp(_flash_fwd, _flash_bwd)


DEFAULT_BLOCK_Q_BWD = 256
DEFAULT_BLOCK_K_BWD = 1024


def auto_blocks(T: int):
    """Block policy of the CLASSIC grid kernels (what non-causal calls
    and sequence lengths the triangle tile does not divide run): stream
    the WHOLE key axis per q-tile whenever the f32 score tile fits VMEM,
    bq capped at 1024.  Measured on a v5e (PR 29, builder's kernel-alone
    timings at (288, 1024, 64) bf16, forward / backward ms, each with
    ~0.6 / ~1.2 ms of the harness's own in it): (1024, 1024) 1.9 / 4.4;
    (512, 512) 2.8 / 4.7; (256, 256) 4.6 / 8.0; (128, 128) 8.8 / 15.9.
    A kv grid dimension costs 0.4-0.5 us a grid step (18,432 steps at
    128s) on top of the (bq, 1) softmax state's round trip through VMEM
    scratch, one lane of 128 in use: that, not a cliff, is the "~10x"
    an earlier note here reported for nk>1.  Its "bq=512 pathology
    (1766 ms vs 21.7 ms at T=2048-class shapes)" is not there on this
    toolchain: (512, 2048) reads 3.55 / 7.34 ms beside (256, 2048)
    3.64 / 7.89 at (144, 2048, 64).  Past T=2048 the (1024, T) tile no
    longer compiles, so kv streaming is unavoidable there.
    Returns (block_q, block_k, block_q_bwd, block_k_bwd)."""
    if T <= 2048:
        return min(1024, T), T, 256, T
    return 1024, 1024, 256, 1024


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    block_q_bwd: Optional[int] = None,
                    block_k_bwd: Optional[int] = None,
                    resident_kv: Optional[bool] = None,
                    interpret: bool = False) -> jnp.ndarray:
    """Flash attention on (B, T, H, D) tensors.  Differentiable; HBM use
    is O(T).  With nothing but `causal` given, the kernels are chosen
    from causal, T, D and dtype: the triangle kernels for causal
    T <= 2048 (only the tiles on or under the diagonal are computed;
    _triangle_plan), the resident-kv kernels for causal T > 2048
    (_resident_plan), else the classic grid kernels at auto_blocks(T).

    Explicit `block_*` arguments pin the classic grid kernels (an
    explicit VMEM-budget tuning): forward blocks also govern the
    backward unless backward blocks are set too.

    resident_kv: True = whole-T k/v resident in VMEM with an in-kernel
    causal-early-stop kv loop; False = classic grid kernels; None = the
    auto policy above.  The RAYTPU_FLASH_RESIDENT env var ("1"/"0")
    stands in for None."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    def from3(o3):
        return o3.reshape(B, H, T, D).transpose(0, 2, 1, 3)

    if resident_kv is None:
        # the RAYTPU_FLASH_RESIDENT env var overrides auto dispatch
        resident_kv = resolve_resident_mode("auto")
    # any explicit block tuning (fwd or bwd) pins the classic path
    tuned = not (block_q is None and block_k is None
                 and block_q_bwd is None and block_k_bwd is None)
    tiles = _triangle_plan(T, D, q.dtype, causal)
    if resident_kv is None and not tuned and tiles is not None:
        return from3(_flash_tri(to3(q), to3(k), to3(v), scale, *tiles,
                                interpret))
    if resident_kv is None:
        resident_kv = not tuned and _resident_plan(T, causal) is not None
    if resident_kv:
        bq_r, bk_r, chunk = _resident_plan(T, causal) or (
            _blocks(T, RESIDENT_BLOCK_Q), _blocks(T, RESIDENT_BLOCK_Q),
            _blocks(T, RESIDENT_CHUNK))
        return from3(_flash_res(to3(q), to3(k), to3(v), scale, bq_r, bk_r,
                                chunk, causal, interpret))

    auto_q, auto_k, auto_qb, auto_kb = auto_blocks(T)
    if block_q is None and block_k is None:
        block_q, block_k = auto_q, auto_k
        if block_q_bwd is None:
            block_q_bwd = auto_qb
        if block_k_bwd is None:
            block_k_bwd = auto_kb
    else:
        block_q = block_q or auto_q
        block_k = block_k or auto_k
        if block_q_bwd is None:
            block_q_bwd = block_q
        if block_k_bwd is None:
            block_k_bwd = block_k

    return from3(_flash(to3(q), to3(k), to3(v), scale, block_q, block_k,
                        causal, interpret, block_q_bwd, block_k_bwd))
