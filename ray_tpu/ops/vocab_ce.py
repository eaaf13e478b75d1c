"""Streaming (vocab-tiled) cross-entropy for tied-embedding LM heads.

The naive path materializes float32 logits of shape (B, T, V) — at
B=32, T=1024, V=50304 that is a 6.6 GB HBM round-trip per step, the
single largest non-matmul cost in the GPT-2 step (ROADMAP.md A2).
This module computes ``mean_ce(h @ wte^T, targets)`` WITHOUT ever
materializing the full logits: a ``lax.scan`` over vocab tiles keeps
one (N, Vt) tile live at a time, maintaining an online logsumexp
(FlashAttention-style running max/sum) plus the target logit picked by
masked reduction.  The custom VJP recomputes each tile in the backward
scan — dh accumulates across tiles, dwte is emitted per tile — so the
peak activation footprint is O(N * Vt) in both passes.

Pure XLA by design: every tile step is one bf16 GEMM (MXU) plus fused
elementwise, which the compiler pipelines; no Mosaic kernel needed, so
it also runs under a multi-device mesh, where the fused pallas CE
(ops/fused_ce.py) does not.

Reference: the role of fused CE kernels in large-vocab trainers
(e.g. the reference's torch stack leans on fused CUDA CE losses); the
online-logsumexp recurrence is the standard streaming-softmax identity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax


def _pad_table(wte, tile: int):
    """Round the table up to a tile multiple with zero rows (they sit
    beyond valid_vocab, so the mask hides them)."""
    v, d = wte.shape
    rem = (-v) % tile
    if rem:
        wte = jnp.concatenate(
            [wte, jnp.zeros((rem, d), wte.dtype)], axis=0)
    return wte, v + rem


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def streaming_ce(hidden, wte, targets, valid_vocab: int,
                 vocab_tile: int = 8192, compute_dtype=jnp.bfloat16):
    """Per-token cross entropy of tied-head logits, vocab-streamed.

    hidden: (N, D) — flattened (B*T, D) activations.
    wte: (V, D) embedding table (V = padded vocab, tiled by vocab_tile).
    targets: (N,) int32 in [0, valid_vocab).
    valid_vocab: logits at indices >= valid_vocab are masked to -inf.

    Returns (N,) float32 nll.  Differentiable w.r.t. hidden and wte.
    """
    nll, _ = _forward(hidden, wte, targets, valid_vocab, vocab_tile,
                      compute_dtype)
    return nll


def _forward(hidden, wte, targets, valid_vocab, vocab_tile,
             compute_dtype):
    n, d = hidden.shape
    wte_p, v = _pad_table(wte, vocab_tile)
    t = v // vocab_tile
    h = hidden.astype(compute_dtype)
    w_tiles = wte_p.reshape(t, vocab_tile, d).astype(compute_dtype)

    def tile_step(carry, inputs):
        m, s, tgt = carry                       # (N,) f32 each
        w_tile, tile_idx = inputs
        # one (N, Vt) bf16 GEMM with f32 accumulation — the only place
        # a logits tile ever exists, and only in registers/VMEM scope
        logits = jnp.dot(h, w_tile.T,
                         preferred_element_type=jnp.float32)
        base = tile_idx * vocab_tile
        col = base + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(col < valid_vocab, logits, -jnp.inf)
        # online logsumexp merge
        tile_max = jnp.max(logits, axis=1)
        new_m = jnp.maximum(m, tile_max)
        s = s * jnp.exp(m - new_m) + jnp.sum(
            jnp.exp(logits - new_m[:, None]), axis=1)
        # target pick: exactly one tile contains each row's target
        tgt = tgt + jnp.sum(
            jnp.where(col == targets[:, None], logits, 0.0), axis=1)
        return (new_m, s, tgt), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.float32))
    (m, s, tgt), _ = lax.scan(
        tile_step, init, (w_tiles, jnp.arange(t, dtype=jnp.int32)))
    lse = m + jnp.log(s)
    return lse - tgt, lse


def _fwd(hidden, wte, targets, valid_vocab, vocab_tile, compute_dtype):
    nll, lse = _forward(hidden, wte, targets, valid_vocab, vocab_tile,
                        compute_dtype)
    return nll, (hidden, wte, targets, lse)


def _bwd(valid_vocab, vocab_tile, compute_dtype, res, g):
    """g: (N,) cotangent of nll.  dlogits = g * (softmax - onehot),
    recomputed tile-by-tile; dh accumulates across tiles, dwte is
    emitted per tile (the scan's ys) and reshaped to (V, D)."""
    hidden, wte, targets, lse = res
    n, d = hidden.shape
    wte_p, v = _pad_table(wte, vocab_tile)
    t = v // vocab_tile
    h = hidden.astype(compute_dtype)
    w_tiles = wte_p.reshape(t, vocab_tile, d).astype(compute_dtype)
    gf = g.astype(jnp.float32)

    def tile_step(dh, inputs):
        w_tile, tile_idx = inputs
        logits = jnp.dot(h, w_tile.T,
                         preferred_element_type=jnp.float32)
        base = tile_idx * vocab_tile
        col = base + lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(col < valid_vocab, logits, -jnp.inf)
        p = jnp.exp(logits - lse[:, None])      # softmax tile
        dlog = jnp.where(col == targets[:, None], p - 1.0, p)
        dlog = (dlog * gf[:, None]).astype(compute_dtype)
        dh = dh + jnp.dot(dlog, w_tile,
                          preferred_element_type=jnp.float32)
        dw_tile = jnp.dot(dlog.T, h,
                          preferred_element_type=jnp.float32)
        return dh, dw_tile

    dh, dw_tiles = lax.scan(
        tile_step, jnp.zeros((n, d), jnp.float32),
        (w_tiles, jnp.arange(t, dtype=jnp.int32)))
    dwte = dw_tiles.reshape(v, d)[:wte.shape[0]]  # drop pad rows
    return (dh.astype(hidden.dtype), dwte.astype(wte.dtype), None)


streaming_ce.defvjp(_fwd, _bwd)
