"""Flash attention for TPU, written in pallas.

Blockwise online-softmax attention (the FlashAttention recurrence): the
T×T score matrix never materializes in HBM, and VMEM holds only one
(block_q, block_k) tile of work at a time.  The kv loop is a grid
dimension — pallas double-buffers the k/v block DMAs against compute —
and the online-softmax state (m, l, acc) lives in VMEM scratch that
persists across the sequentially-executed kv grid steps.  Both matmuls
hit the MXU with float32 accumulation.  Causal masking skips
fully-masked tiles (`pl.when`), so the causal kernel does ~half the
FLOPs.

Backward is the standard recompute scheme: forward saves only O(T) row
statistics (logsumexp); two kernels recompute score tiles on the fly —
one accumulates dq over kv blocks, one accumulates dk/dv over q blocks —
so backward memory is O(T) as well.

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

# Whole-1024 tiles measured fastest on v5e at GPT-2 shapes (T=1024,
# D=64): one tile per (batch*head) avoids the online-softmax revisit
# overhead and still fits VMEM (4 MiB f32 score tile).  _blocks() caps
# these to T, and longer sequences fall back to multi-tile streaming.
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
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (BH, 1, T)

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
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]           # (BH, 1, T)

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
    """Pick the resident-kv configuration for seq length T, or None when
    the classic grid kernels should run instead.  Measured v5e policy:
    at T=1024 resident+causal-skip beats the whole-T tile (6.1ms vs
    7.5ms fwd at B=24 H=12); at T=2048 the whole-T tile's bigger MXU
    tiles win, so the classic path keeps it; past T=2048 the whole-T
    score tile no longer compiles (scoped-vmem OOM at (1024, 4096)) and
    resident kv is what makes long single-chip sequences viable at all.

    GATING: the resident forward AND backward kernels compile for the
    chip and run on it — chip_smoke.py runs both at (2*12, 1024, 64)
    bf16 on a v5e and they agree with the classic kernels and the XLA
    reference to the last printed digit.  What is not measured is which
    is faster inside the full train step, so AUTO dispatch at T<=2048
    stays on the classic kernels until that A/B is in the ledger
    (ROADMAP.md A2); opt in per-config (flash_resident="on") or
    per-process (RAYTPU_FLASH_RESIDENT=1, resolved by
    resolve_resident_mode into an explicit resident_kv=True).  T>2048
    stays auto-resident (the classic tile cannot compile there at all).
    Returns (bq, bk, chunk) or None."""
    if not causal:
        return None                 # no skip to win; classic path
    if T % RESIDENT_CHUNK or T % RESIDENT_BLOCK_Q:
        return None
    if T <= 2048:
        return None                 # classic until the step A/B is measured
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
    """Measured-on-v5e block policy: stream the WHOLE key axis per q-tile
    whenever the f32 score tile fits VMEM (nk>1 — online-softmax scratch
    revisits across kv grid steps — costs ~10x on this toolchain), with
    bq capped at 1024 (bq=512 is a measured mosaic pathology: 1766ms vs
    21.7ms at T=2048-class shapes).  Past T=2048 the (1024, T) tile no
    longer compiles, so kv streaming is unavoidable; per-shard sequence
    lengths under ring attention stay <= 2048 and remain on the happy
    path.  Returns (block_q, block_k, block_q_bwd, block_k_bwd)."""
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
    """Flash attention on (B, T, H, D) tensors.  Differentiable; VMEM use
    is O(block), HBM use O(T); causal masking skips ~half the tiles.
    Defaults (None) come from auto_blocks(T) — the measured v5e policy;
    explicitly set forward blocks also govern the backward unless
    backward blocks are set too (an explicit VMEM-budget tuning governs
    both passes).

    resident_kv: True = whole-T k/v resident in VMEM with an in-kernel
    causal-early-stop kv loop (skips ~(1 - (qi+1)/nq) of the score work
    per q tile); False = classic grid kernels; None = measured auto
    policy (_resident_plan).  Explicit block settings imply the classic
    path unless resident_kv=True."""
    B, T, H, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)

    def to3(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, D)

    if resident_kv is None:
        # the RAYTPU_FLASH_RESIDENT env var overrides auto dispatch
        resident_kv = resolve_resident_mode("auto")
    if resident_kv is None:
        # any explicit block tuning (fwd or bwd) pins the classic path
        resident_kv = (block_q is None and block_k is None
                       and block_q_bwd is None and block_k_bwd is None
                       and _resident_plan(T, causal) is not None)
    if resident_kv:
        bq_r, bk_r, chunk = _resident_plan(T, causal) or (
            _blocks(T, RESIDENT_BLOCK_Q), _blocks(T, RESIDENT_BLOCK_Q),
            _blocks(T, RESIDENT_CHUNK))
        o3 = _flash_res(to3(q), to3(k), to3(v), scale, bq_r, bk_r,
                        chunk, causal, interpret)
        return o3.reshape(B, H, T, D).transpose(0, 2, 1, 3)

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

    o3 = _flash(to3(q), to3(k), to3(v), scale, block_q, block_k, causal,
                interpret, block_q_bwd, block_k_bwd)
    return o3.reshape(B, H, T, D).transpose(0, 2, 1, 3)
