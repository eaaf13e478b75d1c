"""Pallas-fused LM-head matmul + cross-entropy (MXU-streamed vocab tiles).

The third and fastest of the repo's CE implementations (the knob is
``GPT2Config.ce_impl``):

  * ``dense``         — materialize float32 (B, T, V) logits (simple; the
                        6.6 GB HBM round-trip at b32/V50k caps batch).
  * ``streaming_xla`` — ops/vocab_ce.py: a ``lax.scan`` over vocab tiles.
                        Kills the logits tensor but each tile round-trips
                        through HBM between the GEMM and the elementwise
                        merge, measured ~3% SLOWER than dense at equal
                        batch (ROADMAP.md A2, builder run 2026-07-31).
  * ``pallas``        — this module: one kernel per (hidden_tile,
                        vocab_tile) grid cell streams the GEMM through the
                        MXU and merges the online-logsumexp state in VMEM
                        scratch that persists across the sequentially
                        executed vocab grid steps.  The logits tile lives
                        only in VMEM; nothing (N, V)-shaped ever exists in
                        either pass.

Backward is the recompute scheme proven out by flash_attention.py: two
kernels re-run the tile GEMMs on the fly — one accumulates ``dhidden``
over vocab tiles in VMEM scratch (flushed once per hidden tile), one
accumulates ``dwte`` over hidden tiles (flushed once per vocab tile; the
TPU grid is sequential, so scratch accumulation across grid steps is
safe).  A fused single-pass backward is deliberately NOT attempted: the
flash kernels measured revisited output blocks at ~10x on this
toolchain.

Compute contract matches the rest of the stack: bf16 (``compute_dtype``)
operands on the MXU with float32 accumulation; the online max/sum/target
accumulators are float32 VMEM scratch.

``interpret=None`` picks the pallas interpreter when the process's
default backend is not a TPU, so tier-1 checks full fwd/bwd numerics on
the CPU through the model configs.  Code whose target IS a chip
(``chip_smoke.py``, the AOT compiles in tests/test_tpu_compile.py, which
run on a CPU host for a described TPU) passes ``interpret=False`` and
checks for ``tpu_custom_call`` in the compiled text, so the interpreter
can never stand in for the kernel unnoticed there.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

# Defaults sized for GPT-2-class shapes (D=768) on v5e VMEM: the w tile
# (1024, 768) bf16 is 1.5 MiB (double-buffered by pallas), the f32
# logits tile (256, 1024) is 1 MiB, and the bwd dw scratch (1024, 768)
# f32 is 3 MiB — comfortably inside the 16 MiB budget.  bq=512-style
# mosaic pathologies (ROADMAP.md A2) argue for 256/1024 over squarer
# tiles.
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_V = 1024
_NEG_INF = -1e30


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _ceil_to(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _logits_tile(h, w, col, valid_vocab: int):
    """One (bn, bv) f32 logits tile: MXU GEMM + padded-tail mask."""
    logits = lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return jnp.where(col < valid_vocab, logits, _NEG_INF)


def _tile_cols(vi, block_n: int, block_v: int):
    return vi * block_v + lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, tgt_ref, nll_ref, lse_ref, m_scr, s_scr,
                t_scr, *, block_n: int, block_v: int, valid_vocab: int,
                num_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        s_scr[:] = jnp.zeros_like(s_scr)
        t_scr[:] = jnp.zeros_like(t_scr)

    col = _tile_cols(vi, block_n, block_v)
    logits = _logits_tile(h_ref[:], w_ref[:], col, valid_vocab)
    # online logsumexp merge (FlashAttention-style running max/sum)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, jnp.max(logits, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    m_scr[:] = m_new
    s_scr[:] = s_scr[:] * alpha + jnp.sum(jnp.exp(logits - m_new),
                                          axis=1, keepdims=True)
    # target pick: exactly one vocab tile contains each row's target
    tgt = tgt_ref[0, :]
    t_scr[:] = t_scr[:] + jnp.sum(
        jnp.where(col == tgt[:, None], logits, 0.0), axis=1,
        keepdims=True)

    @pl.when(vi == num_v - 1)
    def _flush():
        lse = m_scr[:] + jnp.log(s_scr[:])
        lse_ref[0, :] = lse[:, 0]
        nll_ref[0, :] = (lse - t_scr[:])[:, 0]


def _fwd(hp, wp, tgt2, valid_vocab, block_n, block_v, compute_dtype,
         interpret):
    """hp (N, D), wp (V, D), tgt2 (1, N) — all pre-padded to block
    multiples.  Returns nll (N,) f32 and lse (N,) f32."""
    n, d = hp.shape
    v = wp.shape[0]
    nn, nv = n // block_n, v // block_v
    h = hp.astype(compute_dtype)
    w = wp.astype(compute_dtype)
    kern = functools.partial(_fwd_kernel, block_n=block_n,
                             block_v=block_v, valid_vocab=valid_vocab,
                             num_v=nv)
    nll, lse = pl.pallas_call(
        kern,
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_v, d), lambda ni, vi: (vi, 0)),
            pl.BlockSpec((1, block_n), lambda ni, vi: (0, ni)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_n), lambda ni, vi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, vi: (0, ni)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[_vmem((block_n, 1)), _vmem((block_n, 1)),
                        _vmem((block_n, 1))],
        interpret=interpret,
    )(h, w, tgt2)
    return nll[0], lse[0]


# ---------------------------------------------------------------------------
# Backward (tile recompute; dlogits = g * (softmax - onehot))
# ---------------------------------------------------------------------------

def _dlog_tile(h, w, tgt, lse, g, vi, block_n, block_v, valid_vocab):
    """Recompute one (bn, bv) dlogits tile in f32."""
    col = _tile_cols(vi, block_n, block_v)
    logits = _logits_tile(h, w, col, valid_vocab)
    p = jnp.exp(logits - lse[:, None])
    dlog = jnp.where(col == tgt[:, None], p - 1.0, p)
    return dlog * g[:, None]


def _bwd_dh_kernel(h_ref, w_ref, tgt_ref, lse_ref, g_ref, dh_ref, dh_scr,
                   *, block_n: int, block_v: int, valid_vocab: int,
                   num_v: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        dh_scr[:] = jnp.zeros_like(dh_scr)

    w = w_ref[:]
    dlog = _dlog_tile(h_ref[:], w, tgt_ref[0, :], lse_ref[0, :],
                      g_ref[0, :], vi, block_n, block_v, valid_vocab)
    dh_scr[:] = dh_scr[:] + lax.dot_general(
        dlog.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(vi == num_v - 1)
    def _flush():
        dh_ref[:] = dh_scr[:].astype(dh_ref.dtype)


def _bwd_dw_kernel(h_ref, w_ref, tgt_ref, lse_ref, g_ref, dw_ref, dw_scr,
                   *, block_n: int, block_v: int, valid_vocab: int,
                   num_n: int):
    vi = pl.program_id(0)
    ni = pl.program_id(1)

    @pl.when(ni == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    h = h_ref[:]
    dlog = _dlog_tile(h, w_ref[:], tgt_ref[0, :], lse_ref[0, :],
                      g_ref[0, :], vi, block_n, block_v, valid_vocab)
    dw_scr[:] = dw_scr[:] + lax.dot_general(
        dlog.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ni == num_n - 1)
    def _flush():
        dw_ref[:] = dw_scr[:].astype(dw_ref.dtype)


def _bwd(hp, wp, tgt2, lse, g, valid_vocab, block_n, block_v,
         compute_dtype, interpret):
    n, d = hp.shape
    v = wp.shape[0]
    nn, nv = n // block_n, v // block_v
    h = hp.astype(compute_dtype)
    w = wp.astype(compute_dtype)
    lse2 = lse.reshape(1, n)
    g2 = g.astype(jnp.float32).reshape(1, n)

    dh = pl.pallas_call(
        functools.partial(_bwd_dh_kernel, block_n=block_n,
                          block_v=block_v, valid_vocab=valid_vocab,
                          num_v=nv),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda ni, vi: (ni, 0)),
            pl.BlockSpec((block_v, d), lambda ni, vi: (vi, 0)),
            pl.BlockSpec((1, block_n), lambda ni, vi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, vi: (0, ni)),
            pl.BlockSpec((1, block_n), lambda ni, vi: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda ni, vi: (ni, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        scratch_shapes=[_vmem((block_n, d))],
        interpret=interpret,
    )(h, w, tgt2, lse2, g2)

    dw = pl.pallas_call(
        functools.partial(_bwd_dw_kernel, block_n=block_n,
                          block_v=block_v, valid_vocab=valid_vocab,
                          num_n=nn),
        grid=(nv, nn),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda vi, ni: (ni, 0)),
            pl.BlockSpec((block_v, d), lambda vi, ni: (vi, 0)),
            pl.BlockSpec((1, block_n), lambda vi, ni: (0, ni)),
            pl.BlockSpec((1, block_n), lambda vi, ni: (0, ni)),
            pl.BlockSpec((1, block_n), lambda vi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_v, d), lambda vi, ni: (vi, 0)),
        out_shape=jax.ShapeDtypeStruct((v, d), jnp.float32),
        scratch_shapes=[_vmem((block_v, d))],
        interpret=interpret,
    )(h, w, tgt2, lse2, g2)
    return dh.astype(hp.dtype), dw.astype(wp.dtype)


# ---------------------------------------------------------------------------
# custom VJP core (block-aligned shapes) + public padding wrapper
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce(hp, wp, tgt2, valid_vocab, block_n, block_v, compute_dtype,
              interpret):
    nll, _ = _fwd(hp, wp, tgt2, valid_vocab, block_n, block_v,
                  compute_dtype, interpret)
    return nll


def _fused_ce_fwd(hp, wp, tgt2, valid_vocab, block_n, block_v,
                  compute_dtype, interpret):
    nll, lse = _fwd(hp, wp, tgt2, valid_vocab, block_n, block_v,
                    compute_dtype, interpret)
    return nll, (hp, wp, tgt2, lse)


def _fused_ce_bwd(valid_vocab, block_n, block_v, compute_dtype, interpret,
                  res, g):
    hp, wp, tgt2, lse = res
    dh, dw = _bwd(hp, wp, tgt2, lse, g, valid_vocab, block_n, block_v,
                  compute_dtype, interpret)
    return dh, dw, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def fused_lm_ce(hidden, wte, targets, valid_vocab: int, *,
                block_n: int = DEFAULT_BLOCK_N,
                block_v: int = DEFAULT_BLOCK_V,
                compute_dtype=jnp.bfloat16,
                interpret=None) -> jnp.ndarray:
    """Per-token CE of ``hidden @ wte^T`` logits, fused in one pallas pass.

    hidden: (N, D) — flattened (B*T, D) activations.
    wte: (V, D) vocab-major head table (tied ``wte``, or a transposed
        ``lm_head`` for untied models); rows >= valid_vocab are masked.
    targets: (N,) int32 in [0, valid_vocab).
    interpret: None = by the default backend (Mosaic kernel on a TPU,
        pallas interpreter elsewhere); False = always the Mosaic kernel
        (what a chip run or an AOT compile for a chip must pass).

    Returns (N,) float32 nll, differentiable w.r.t. hidden and wte.  The
    (N, V) logits never exist in HBM in either pass; peak live state is
    one (block_n, block_v) f32 tile + f32 accumulators in VMEM.  Inputs
    are zero-padded up to block multiples (padded rows/cols are masked
    out and receive zero gradient via the pad/slice transpose).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, d = hidden.shape
    v = wte.shape[0]
    if not 0 < valid_vocab <= v:
        raise ValueError(f"valid_vocab={valid_vocab} must be in "
                         f"(0, {v}] for a (V={v}, D) head table")
    block_n = min(block_n, _ceil_to(n, 16))
    block_v = min(block_v, _ceil_to(v, 128))
    n_pad = _ceil_to(n, block_n) - n
    v_pad = _ceil_to(v, block_v) - v
    hp = jnp.pad(hidden, ((0, n_pad), (0, 0))) if n_pad else hidden
    wp = jnp.pad(wte, ((0, v_pad), (0, 0))) if v_pad else wte
    tgt2 = jnp.pad(targets.astype(jnp.int32),
                   (0, n_pad)).reshape(1, n + n_pad)
    nll = _fused_ce(hp, wp, tgt2, valid_vocab, block_n, block_v,
                    compute_dtype, interpret)
    return nll[:n]
