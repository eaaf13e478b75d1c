"""Kimi Delta Attention (KDA): a gated delta rule whose state is a
float32 matrix a head.

Per head, with keys and queries of ``dk`` and values of ``dv``, the
state ``S`` (dk, dv) takes one token so:

    S' = Diag(exp(g_t)) S_{t-1}                  g_t <= 0, per CHANNEL
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     a rank-one correction
    o_t = S_t^T q_t

(arXiv 2510.26692; ``beta`` may reach 2, where ``I - beta k k^T`` has
an eigenvalue of -1).  Three forms of it, plain ``jax.numpy`` / ``lax``:

  * `kda_recurrent`: the recurrence itself, a scan over time in
    float32: the oracle.
  * `kda_step`: one token a row, for a decode wave.
  * `kda_chunked`: a prefill's.  A chunk of ``C`` tokens enters with
    ``S_0``; with ``G_r = sum_{i <= r} g_i`` per channel,

        K+_i = k_i exp(G_i)         Q+_r = q_r exp(G_r)
        K-_i = k_i exp(G_C - G_i)
        A_ij = (k_i exp(G_i - G_j)) . k_j      j <  i
        B_ri = (q_r exp(G_r - G_i)) . k_i      i <= r

    solve the unit lower-triangular ``(I + Diag(beta) A) U =
    Diag(beta) (V - K+ S_0)``; then ``O = Q+ S_0 + B U`` and ``S_C =
    Diag(exp(G_C)) S_0 + K-^T U``.

Every exponent above is <= 0, and the code keeps it so.  ``exp(-G_j)``
is never formed alone: the factored ``(k_i e^{G_i}) . (k_j e^{-G_j})``
overflows float32 under a strong decay.  The pairwise decays
``exp(G_i - G_j)`` are (C, C, dk) a head, so they are formed only
inside sub-chunks of ``sub`` tokens (the diagonal blocks of ``A`` and
``B``); a block BELOW the diagonal is a matmul factored around the
cumulative decay ``R`` at the later sub-chunk's first token:
``(k_i e^{G_i - R}) . (k_j e^{R - G_j})``, where ``G_i - R`` sums the
log-decays from that token to ``i`` and ``R - G_j`` those from ``j + 1``
to it: both sums of non-positive terms, whatever the decay.  The chunks
are walked in a `lax.scan` (they depend on each other through ``S_0``).

A pad position is an identity step: ``beta = 0`` and ``g = 0`` (its
``k``, ``q`` and ``v`` then move nothing), which is how `kda_chunked`
fills a length that is no multiple of the chunk.

Matmul operands are cast to `dtype` (bfloat16 when serving) and
accumulate in float32; the state, ``G``, the decays and the triangular
solve stay float32.  With `dtype` float32 every product is taken at the
highest precision (a TPU's default rounds float32 operands to bf16).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes

__all__ = ["kda_recurrent", "kda_step", "kda_chunked"]

_F32 = jnp.float32


def kda_step(q, k, v, g, beta, state):
    """One token a row.  q, k, g (B, H, dk); v (B, H, dv); beta (B, H);
    state (B, H, dk, dv) float32.  Returns (o (B, H, dv) float32, the
    new state).  Multiplies and sums in float32, no matmul: a row's
    state is read once and written once, which is all a decode wave's
    delta rule costs."""
    q, k, v, g = (a.astype(_F32) for a in (q, k, v, g))
    decayed = state.astype(_F32) * jnp.exp(g)[..., None]
    seen = jnp.sum(decayed * k[..., None], axis=-2)            # S'^T k
    delta = beta.astype(_F32)[..., None] * (v - seen)
    new = decayed + k[..., None] * delta[..., None, :]
    return jnp.sum(new * q[..., None], axis=-2), new


def kda_recurrent(q, k, v, g, beta, state=None):
    """The recurrence over time.  q, k, g (B, T, H, dk); v (B, T, H,
    dv); beta (B, T, H); state (B, H, dk, dv) or None (zeros).  Returns
    (o (B, T, H, dv) float32, the state after the last token)."""
    B, _, H, dk = k.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), _F32)

    def step(s, xs):
        o, s = kda_step(*xs, s)
        return s, o

    state, o = lax.scan(step, state.astype(_F32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def _solve_unit_lower(low, rhs, sub: int):
    """``(I + low) U = rhs`` for strictly lower-triangular `low` (...,
    C, C) and rhs (..., C, dv), float32: forward substitution by
    sub-chunks of `sub` rows.  Each diagonal block is inverted row by
    row (``sub - 1`` static steps, every block of every head at once),
    then a sub-chunk's rows follow from the ones above in two matmuls.
    Forward substitution and not the product ``(I - L)(I + L^2)(I +
    L^4)...``: with equal keys and ``beta`` = 2 the powers of ``L``
    reach 2^k C(C, k) beside a solution of magnitude 2."""
    hi = lax.Precision.HIGHEST
    C = low.shape[-1]
    n = C // sub
    lead = low.shape[:-2]
    blocks = low.reshape(*lead, n, sub, n, sub)
    diag = jnp.stack([blocks[..., s, :, s, :] for s in range(n)], axis=-3)
    # rows of (I + diag)^-1: row i = e_i - diag[i, :i] @ rows[:i]
    rows = [jnp.broadcast_to(jnp.eye(sub, dtype=_F32)[0], (*lead, n, sub))]
    for i in range(1, sub):
        above = jnp.stack(rows, axis=-2)                 # (..., n, i, sub)
        rows.append(jnp.eye(sub, dtype=_F32)[i] - jnp.einsum(
            "...j,...jc->...c", diag[..., i, :i], above, precision=hi))
    inv = jnp.stack(rows, axis=-2)                     # (..., n, sub, sub)
    out = []
    for s in range(n):
        r = rhs[..., s * sub:(s + 1) * sub, :]
        if s:
            r = r - jnp.einsum(
                "...ij,...jv->...iv",
                low[..., s * sub:(s + 1) * sub, :s * sub],
                jnp.concatenate(out, axis=-2), precision=hi)
        out.append(jnp.einsum("...ij,...jv->...iv", inv[..., s, :, :], r,
                              precision=hi))
    return jnp.concatenate(out, axis=-2)


def _chunk(q, k, v, g, beta, s0, sub: int, dtype, capture=None):
    """One chunk, heads leading: q, k, g (B, H, C, dk) float32; v (B, H,
    C, dv); beta (B, H, C); s0 (B, H, dk, dv) float32.  Returns (o (B,
    H, C, dv) float32, the state after the chunk, the state after row
    `capture` of it or None)."""
    C, dk = k.shape[-2:]
    n = C // sub
    lead = k.shape[:-2]
    prec = lax.Precision.HIGHEST if dtype == _F32 else None

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          precision=prec, preferred_element_type=_F32)

    G = jnp.cumsum(g, axis=-2)                                # (.., C, dk)
    # R[s]: the cumulative log-decay before sub-chunk s's first token
    R = jnp.concatenate([jnp.zeros_like(G[..., :1, :]),
                         G[..., sub - 1:C - 1:sub, :]], axis=-2)
    inner = (G.reshape(*lead, n, sub, dk) - R[..., :, None, :])  # <= 0
    ks, qs = (a.reshape(*lead, n, sub, dk) for a in (k, q))

    # the diagonal blocks, pairwise: exp(G_a - G_b) for b <= a
    tri = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        tri[:, :, None], inner[..., :, None, :] - inner[..., None, :, :],
        -jnp.inf))                                  # (.., n, sub, sub, dk)
    pair = decay * ks[..., None, :, :]
    a_diag = jnp.sum(pair * ks[..., :, None, :], axis=-1)
    b_diag = jnp.sum(pair * qs[..., :, None, :], axis=-1)
    eye = jnp.eye(n, dtype=_F32)[:, None, :, None]

    def on_diagonal(blocks):            # (.., n, sub, sub) -> (.., C, C)
        return (blocks[..., :, :, None, :] * eye).reshape(*lead, C, C)

    # the blocks below it, factored around R: both exponents <= 0
    before = jnp.arange(C)[None, :] < (jnp.arange(n) * sub)[:, None]
    k_dec = k[..., None, :, :] * jnp.exp(jnp.where(
        before[:, :, None], R[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))                                     # (.., n, C, dk)
    grown = jnp.exp(inner)
    A = mm("...sid,...sjd->...sij", ks * grown, k_dec).reshape(
        *lead, C, C) + on_diagonal(
            jnp.tril(a_diag, -1))
    Bm = mm("...sid,...sjd->...sij", qs * grown, k_dec).reshape(
        *lead, C, C) + on_diagonal(b_diag)

    whole = jnp.exp(G)
    rhs = beta[..., None] * (v - mm("...cd,...dv->...cv", k * whole, s0))
    U = _solve_unit_lower(beta[..., None] * A, rhs, sub)
    o = mm("...cd,...dv->...cv", q * whole, s0) \
        + mm("...ci,...iv->...cv", Bm, U)

    def state_after(row):
        """The state once rows ``0..row`` of the chunk are in."""
        at = lax.dynamic_index_in_dim(G, row, axis=-2)   # (.., 1, dk)
        kept = jnp.exp(jnp.where(
            (jnp.arange(C) <= row)[:, None], at - G, -jnp.inf))
        return jnp.swapaxes(jnp.exp(at), -1, -2) * s0 \
            + mm("...cd,...cv->...dv", k * kept, U)

    return o, state_after(C - 1), \
        None if capture is None else state_after(capture)


def kda_chunked(q, k, v, g, beta, state=None, *, chunk: int = 64,
                sub: int = 16, dtype=jnp.bfloat16, capture=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """The chunked form (module docstring), shapes as `kda_recurrent`.
    `chunk` a multiple of `sub`; a length that is no multiple of
    `chunk` is filled with identity steps.  `capture`: a traced index
    of the time axis after which the state is handed back too (a
    snapshot), or None.

    Returns (o (B, T, H, dv) float32, the state after the last token,
    the state after token `capture` or None)."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    sub = min(sub, chunk)
    if chunk % sub:
        raise ValueError(f"chunk {chunk} must be a multiple of sub {sub}")
    if state is None:
        state = jnp.zeros((B, H, dk, dv), _F32)
    fill = -T % chunk
    n = (T + fill) // chunk

    def chunks(a):
        """(B, T, H, ...) -> (n, B, H, chunk, ...) float32."""
        a = jnp.pad(a.astype(_F32),
                    ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, n, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    xs = tuple(chunks(a) for a in (q, k, v, g, beta))
    state = state.astype(_F32)
    if capture is not None:
        capture = jnp.asarray(capture, jnp.int32)

    # (a loop's body names its scope again: it is lowered as a function
    # of its own, kimi_k2_decode.attend_blockwise)
    @jax.named_scope(scopes.ATTN_LINEAR)
    def body(carry, x):
        s, snap = carry
        i, x = x[0], x[1:]
        row = None if capture is None \
            else jnp.clip(capture - i * chunk, 0, chunk - 1)
        o, s, s_at = _chunk(*x, s, sub, dtype, row)
        if capture is not None:
            snap = jnp.where(capture // chunk == i, s_at, snap)
        return (s, snap), o

    (state, snap), o = lax.scan(
        body, (state, None if capture is None else state),
        (jnp.arange(n, dtype=jnp.int32),) + xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1).reshape(
        B, n * chunk, H, dv)
    return o[:, :T], state, snap
