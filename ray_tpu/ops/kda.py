"""Kimi Delta Attention (KDA): a gated delta rule whose state is a
float32 matrix a head.

Per head, with keys and queries of ``dk`` and values of ``dv``, the
state ``S`` (dk, dv) takes one token so:

    S' = Diag(exp(g_t)) S_{t-1}                  g_t <= 0, per CHANNEL
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T     a rank-one correction
    o_t = S_t^T q_t

(arXiv 2510.26692; ``beta`` may reach 2, where ``I - beta k k^T`` has
an eigenvalue of -1).  Five forms of it, three of them plain
``jax.numpy`` / ``lax``:

  * `kda_recurrent`: the recurrence itself, a scan over time in
    float32: the oracle.
  * `kda_step`: one token a row, for a decode wave.
  * `kda_decode`: that step on one layer of the KDA layers' STACKED
    state (n, B, H, dk, dv), which is what a decode program carries,
    and what a model calls.  On the chip, where a head is whole lanes,
    it is ONE ``pallas_call`` named ``kda_decode`` a layer, the stack
    aliased to its result: a grid step fetches eight heads' matrices of
    layer ``j`` (a scalar-prefetched index) where they lie, updates
    them in VMEM and writes them back to the same bytes; the other
    layers are not touched and no (B, H, dk, dv) array exists outside
    the call.  XLA runs `kda_step` as three fusions a layer, the
    matrices read three times and written once (``S'^T k`` must be
    summed before the rank-one update can be formed, and ``S_t^T q``
    needs the update); here they are read once and written once, at
    the pace a read and a write through VMEM go at (~600 GB/s of the
    chip's 819).  The arithmetic is `kda_step`'s, float32, in its
    order; `_step_kernel` says how ``k``, ``q`` and ``exp(g)`` become
    the per-sublane factors it needs without a rounding.  ONE decay a
    head has a body of its own, below.  Everywhere else (the CPU, heads
    of 16, a differentiated program) it is `kda_step` on the layer.
  * `kda_chunked`: a prefill's.  A chunk of ``C`` tokens enters with
    ``S_0``; with ``G_r = sum_{i <= r} g_i`` per channel,

        K+_i = k_i exp(G_i)         Q+_r = q_r exp(G_r)
        K-_i = k_i exp(G_C - G_i)
        A_ij = (k_i exp(G_i - G_j)) . k_j      j <  i
        B_ri = (q_r exp(G_r - G_i)) . k_i      i <= r

    solve the unit lower-triangular ``(I + Diag(beta) A) U =
    Diag(beta) (V - K+ S_0)``; then ``O = Q+ S_0 + B U`` and ``S_C =
    Diag(exp(G_C)) S_0 + K-^T U``.

  * the same rule with ONE decay a head (Gated DeltaNet, arXiv
    2412.06464): every form takes ``g`` of trailing size ``dk`` (KDA)
    or of trailing size 1.  The recurrence and the step broadcast it.
    The chunked form then needs no pairwise decay a channel: with
    ``G_r`` a number a head,

        A_ij = (k_i . k_j) exp(G_i - G_j)      j <  i
        B_ri = (q_r . k_i) exp(G_r - G_i)      i <= r

    are ONE product each and a (C, C) mask of non-positive exponents,
    and `_scalar_gate_chunked` is matmuls only.  With ``T = (I +
    Diag(beta) A)^-1``, ``U = W - Y S_0`` where ``W = T Diag(beta) V``
    and ``Y = T Diag(beta exp(G)) K`` need no state; so ``O = B W +
    (exp(G) Q - B Y) S_0`` and ``S_C = exp(G_C) S_0 + K-^T W - (K-^T
    Y) S_0`` with ``K-_i = k_i exp(G_C - G_i)``: everything but the two
    products with ``S_0`` is taken for all chunks of a prefill at once,
    in batched products, and only ``S_0 -> S_C`` and the state's part
    of ``O`` run in the `lax.scan`.  On the chip a decode wave's step is
    the call ``delta_decode`` (`_head_gate_step_kernel`: a row's heads a
    grid step), a prefill's `kda_chunk`'s second body, ``delta_chunk``.

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

  * `kda_chunk`: the chunk form again, as ONE ``pallas_call`` named
    ``kda_chunk`` a layer: `_chunk`'s operations in its order and its
    precisions (below).  XLA runs the scan as ~150 small device ops a
    chunk, the state through HBM once a chunk, the pairwise decays
    written out (33 MB a chunk over 64 heads), the operands re-laid
    heads-leading first.  Here a grid step is (row, eight heads, two
    chunks): a head is a 128-lane slice of the folded rows of q, k, v,
    g, read where they lie; its state stays in VMEM from its first
    chunk to its last; a diagonal block's pairwise decays are formed a
    column against eight rows at a time and summed along the lanes
    where they are formed; ``A``, ``B`` and ``U`` never leave the chip.
    A chunk is a chain of dependent small steps (fifteen row steps of
    the diagonal blocks' inverses, a dozen small products), so the
    group's heads go through it side by side on a leading axis and
    hide each other's latency.  ``G`` and the sub-chunks' own sums are
    products with ones below the diagonal (float32, highest
    precision), the solve `_solve_unit_lower`'s: the diagonal blocks
    inverted row by row, all at once, then a sub-chunk's rows from the
    ones above in two float32 products.  `capture`: the kernel hands
    back the state at the START of the chunk that holds the column and
    `_chunk` does that one chunk again in ``jnp``.
    With ONE decay a head (``g`` of trailing size 1) the same grid,
    solve and capture protocol run another body, a call named
    ``delta_chunk`` (`_head_gate_kernel`): ``G`` is a number a token,
    so ONE (C, C) mask ``exp(G_i - G_j)`` a head (every exponent <= 0)
    turns the one product ``[K; Q] K^T`` into ``A`` and ``B``; there is
    no pairwise decay a channel, no sub-chunk walk and no factoring
    around ``R``; the state enters the solve's right-hand side
    (`_chunk`'s algebra, width ``dv``) and stays in VMEM across the
    head's chunks.  Heads that are no whole lanes (keys of 96, values
    of 192) are filled with zeros to whole lanes on the way in, which
    is exact, and cut back on the way out; the state comes and goes
    (B, H, dk, dv).  `capture`: as above, the one chunk done again by
    `_scalar_gate_chunked`.
  * `kda_prefill` is what a model calls.  A prefill takes one of THREE
    forms, picked from the backend and the shapes, never from a model's
    name: on the chip, more than one column, a chunk of at most 128
    columns in sub-chunks of whole sublane tiles, and then by ``g``:
    trailing size ``dk`` over heads of whole lanes is `kda_chunk`'s
    first body; trailing size 1 over heads that fill at least half of
    the lanes they are padded to is its second; everything else (the
    CPU, heads of 16, one column) is `kda_chunked`.  A differentiated
    `kda_chunk` is `kda_chunked` forward and backward (its
    ``custom_vjp``).  `kda_chunked` is the parity oracle.

A pad position is an identity step: ``beta = 0`` and ``g = 0`` (its
``k``, ``q`` and ``v`` then move nothing), which is how both chunk
forms fill a length that is no multiple of the chunk.

Matmul operands are cast to `dtype` (bfloat16 when serving) and
accumulate in float32; the state, ``G``, the decays and the triangular
solve stay float32.  With `dtype` float32 every product is taken at the
highest precision (a TPU's default rounds float32 operands to bf16).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

__all__ = ["kda_recurrent", "kda_step", "kda_decode", "kda_chunked",
           "kda_chunk", "kda_prefill"]

_F32 = jnp.float32
#: a vreg of float32: 8 sublanes of 128 lanes
_SUBLANES, _LANES = 8, 128
#: chunks a grid step of `kda_chunk` (an inner loop) and heads a grid
#: step (side by side on the leading axis of every operation of a
#: chunk).  What the chip said (my chip run, PR 50; 64 heads of 128,
#: 8,192 columns, one layer; the `jnp` scan 17.5 ms): 1 head 18.2 ms, 2
#: heads 12.8, 4 heads 10.0, 8 heads 9.2, 16 heads 9.0; 1, 2 or 4
#: chunks a step alike
_CHUNKS_A_STEP = 2
_HEADS_A_STEP = 8
#: heads a grid step of the kernel for ONE decay a head
#: (`_head_gate_kernel`), and the VMEM it may take: a head's blocks and
#: state are 1.7 MB at keys of 128 and values of 256 lanes, so fifteen
#: pass the 16 MiB a kernel is given unasked.  What the chip said (my
#: chip run, PR 57; 30 heads of 96 x 192, 6,144 columns, one layer with
#: its fills, re-lays and captured chunk; the `jnp` form 11.93 ms): 5
#: heads 5.13 ms, 6 heads 5.06, 10 heads 4.92, 15 heads 4.83; 1, 2 or
#: 4 chunks a step alike
_HEADS_A_GATE_STEP = 15
_HEAD_GATE_VMEM = 64 * 2 ** 20
#: heads a grid step of `kda_decode`: a sublane tile of the folded
#: operands; their 72 part rows fit one transposed tile (`_step_kernel`)
_HEADS_A_WAVE_STEP = 8


def kda_step(q, k, v, g, beta, state):
    """One token a row.  q, k (B, H, dk); g (B, H, dk), or (B, H, 1):
    one decay a head; v (B, H, dv); beta (B, H); state (B, H, dk, dv)
    float32.  Returns (o (B, H, dv) float32, the
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
    """The recurrence over time.  q, k (B, T, H, dk); g (B, T, H, dk)
    or (B, T, H, 1): one decay a head; v (B, T, H, dv); beta (B, T, H);
    state (B, H, dk, dv) or None (zeros).  Returns
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


def _scalar_gate_chunked(q, k, v, g, beta, state, chunk: int, sub: int,
                         dtype, capture):
    """`kda_chunked` for ONE decay a head, g (B, T, H, 1) (module
    docstring): what needs no state for every chunk at once, heads
    leading, (B, H, n, C, ...); the scan carries the state alone."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    C = chunk
    fill = -T % C
    n = (T + fill) // C
    prec = lax.Precision.HIGHEST if dtype == _F32 else None

    def mm(spec, a, b):
        return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                          precision=prec, preferred_element_type=_F32)

    def chunks(a):
        """(B, T, H, d) -> (B, H, n, C, d) float32."""
        a = jnp.pad(a.astype(_F32), ((0, 0), (0, fill), (0, 0), (0, 0)))
        return jnp.moveaxis(a.reshape(B, n, C, H, -1), 3, 1)

    q, k, v = chunks(q), chunks(k), chunks(v)
    G = jnp.cumsum(chunks(g)[..., 0], axis=-1)              # (B, H, n, C)
    beta = chunks(beta[..., None])
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((C, C), bool)),
                              G[..., :, None] - G[..., None, :], -jnp.inf))
    A = jnp.tril(mm("...id,...jd->...ij", k, k) * decay, -1)
    Bm = mm("...id,...jd->...ij", q, k) * decay
    whole = jnp.exp(G)[..., None]
    solved = _solve_unit_lower(
        beta * A, beta * jnp.concatenate([v, whole * k], axis=-1), sub)
    W, Y = solved[..., :dv], solved[..., dv:]
    last = G[..., -1:]                                      # G_C
    k_end = k * jnp.exp(last - G)[..., None]
    per_chunk = tuple(jnp.moveaxis(a, 2, 0) for a in (
        mm("...ci,...iv->...cv", Bm, W),                    # O's own part
        q * whole - mm("...ci,...id->...cd", Bm, Y),        # ... from S_0
        mm("...cd,...cv->...dv", k_end, W),                 # S_C's own part
        mm("...cd,...ce->...de", k_end, Y),                 # ... from S_0
        jnp.exp(last)[..., None]))
    at = None if capture is None else capture // C

    # (a loop's body names its scope again: it is lowered as a function
    # of its own, kimi_k2_decode.attend_blockwise)
    @jax.named_scope(scopes.ATTN_LINEAR)
    def body(carry, x):
        s, entered = carry
        i, o_own, q_in, s_own, s_in, keep = x
        if capture is not None:
            entered = jnp.where(i == at, s, entered)
        o = o_own + mm("...cd,...dv->...cv", q_in, s)
        s = keep * s + s_own - mm("...de,...ev->...dv", s_in, s)
        return (s, entered), o

    (state, entered), o = lax.scan(
        body, (state, None if capture is None else state),
        (jnp.arange(n, dtype=jnp.int32),) + per_chunk)
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(B, n * C, H, dv)[:, :T]
    if capture is None:
        return o, state, None
    # the state once rows ``0..row`` of chunk `at` are in, from the
    # state that entered it
    k_c, W_c, Y_c, G_c = (lax.dynamic_index_in_dim(a, at, 2, keepdims=False)
                          for a in (k, W, Y, G))
    row = capture - at * C
    G_row = lax.dynamic_index_in_dim(G_c, row, axis=-1)         # (B, H, 1)
    kept = jnp.exp(jnp.where(jnp.arange(C) <= row, G_row - G_c, -jnp.inf))
    U = W_c - mm("...cd,...dv->...cv", Y_c, entered)
    return o, state, jnp.exp(G_row)[..., None] * entered + mm(
        "...cd,...cv->...dv", k_c * kept[..., None], U)


def kda_chunked(q, k, v, g, beta, state=None, *, chunk: int = 64,
                sub: int = 16, dtype=jnp.bfloat16, capture=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """The chunked form (module docstring), shapes as `kda_recurrent`;
    a ``g`` of trailing size 1 takes the matmul form of one decay a
    head.  `chunk` a multiple of `sub`; a length that is no multiple of
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
    state = state.astype(_F32)
    if capture is not None:
        capture = jnp.asarray(capture, jnp.int32)
    if g.shape[-1] == 1 < dk:
        return _scalar_gate_chunked(q, k, v, g, beta, state, chunk, sub,
                                    dtype, capture)
    fill = -T % chunk
    n = (T + fill) // chunk

    def chunks(a):
        """(B, T, H, ...) -> (n, B, H, chunk, ...) float32."""
        a = jnp.pad(a.astype(_F32),
                    ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(B, n, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 2, 3)

    xs = tuple(chunks(a) for a in (q, k, v, g, beta))

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


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

_NT = (((2,), (2,)), ((0,), (0,)))                    # a[h] @ b[h].T


def _kernel_words(heads: int, dtype):
    """What both kernel bodies say a chunk's operations with, the
    group's `heads` heads on a leading axis: (mm, exact, by_head,
    across, rows_of)."""
    hi = lax.Precision.HIGHEST
    prec = hi if dtype == _F32 else None
    plain = (((2,), (1,)), ((0,), (0,)))                 # a[h] @ b[h]

    def mm(a, b, dims=plain):
        return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               precision=prec, preferred_element_type=_F32)

    def exact(a, b):
        return lax.dot_general(a, b, plain, precision=hi,
                               preferred_element_type=_F32)

    def by_head(folded, d):     # (C, heads x d) -> (heads, C, d)
        return jnp.stack([folded[:, h * d:(h + 1) * d]
                          for h in range(heads)])

    def across(a, width):       # (.., 128) alike along the lanes
        return a if width == _LANES else jnp.concatenate(
            [a] * (width // _LANES), axis=-1)

    def rows_of(parts):         # along a chunk's rows
        return jnp.concatenate(parts, axis=1)

    return mm, exact, by_head, across, rows_of


def _solve_in_kernel(rhs, low_off, inv, wide, sub: int, exact, rows_of):
    """`_solve_unit_lower`'s second half on the chip: a sub-chunk's rows
    from the ones above in two float32 products.  rhs (heads, C, dv);
    low_off[s] (heads, sub, C): sub-chunk ``s``'s rows of the strictly
    lower matrix, zero from its own columns on (None for the first);
    inv (heads, sub, 128): the diagonal blocks' inverses, lanes (s,
    column), zero from C on; wide: the lane of a (sub, 128) tile."""
    heads, C, dv = rhs.shape
    n = C // sub
    out = []
    for s in range(n):
        r = rhs[:, s * sub:(s + 1) * sub]
        if s:
            r = r - exact(low_off[s], rows_of(
                out + [jnp.zeros((heads, C - s * sub, dv), _F32)]))
        alone = [jnp.zeros((heads, sub, dv), _F32)] * n \
            + [jnp.zeros((heads, _LANES - C, dv), _F32)] * (C < _LANES)
        alone[s] = r
        out.append(exact(jnp.where(wide // sub == s, inv, 0.0),
                         rows_of(alone)))
    return rows_of(out)                                    # (heads, C, dv)


def _kernel(cap_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref,
            o_ref, st_ref, *rest, chunk: int, sub: int, dk: int, dv: int,
            heads: int, chunks: int, dtype, snapshot: bool):
    """One (row, group of `heads` heads, `chunks` chunks) grid step.
    q, k, g (1, chunks x chunk, heads x dk) and v, o (.., heads x dv):
    the heads' lane slices of the folded rows; b (1, chunks x chunk, H);
    s0, st, snap (1, heads, dk, dv).  Scratch: the heads' states HELD
    TRANSPOSED (heads, dv, dk), so that a chunk's decay of the state is
    a row broadcast along the lanes and ``K+ S`` a product with a
    transposed right side; a chunk's ``G``, its sub-chunks' own
    cumulative sums and its keys (heads, chunk, dk each), rows of which
    are read back one at a time.

    `_chunk`'s operations on a chunk, in its order, float32 but for the
    operands `_chunk`'s ``mm`` casts, the group's heads side by side on
    a leading axis: a chunk is a chain of a dozen small dependent
    products and fifteen row steps, each a few hundred cycles of
    latency, and what hides one head's is another's beside it."""
    from jax.experimental.pallas import tpu as pltpu

    if snapshot:
        snap_ref, s_scr, k_scr, g_scr, in_scr = rest
    else:
        s_scr, k_scr, g_scr, in_scr = rest
    group, t = pl.program_id(1), pl.program_id(2)
    C, n, per = chunk, chunk // sub, sub // _SUBLANES
    hi, nt = lax.Precision.HIGHEST, _NT
    mm, exact, by_head, across, rows_of = _kernel_words(heads, dtype)

    @pl.when(t == 0)
    def _first_chunks():
        for h in range(heads):
            s_scr[h] = s0_ref[0, h].T
            if snapshot:
                snap_ref[0, h] = s0_ref[0, h]

    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # cumulative sums as products with ones below the diagonal: over
    # the chunk (G) and over each sub-chunk alone (G - R, module
    # docstring), every head's at once
    ones_both = jnp.concatenate(
        [(col <= row).astype(_F32),
         ((col <= row) & (row // sub == col // sub)).astype(_F32)], axis=0)
    # ... and beta's column of a head along that head's lanes
    H = b_ref.shape[-1]
    to_lanes = (lax.broadcasted_iota(jnp.int32, (H, heads * _LANES), 0)
                == group * heads + lax.broadcasted_iota(
                    jnp.int32, (H, heads * _LANES), 1) // _LANES
                ).astype(_F32)
    sublane = lax.broadcasted_iota(jnp.int32, (_SUBLANES, 1), 0)
    wide = lax.broadcasted_iota(jnp.int32, (sub, _LANES), 1)
    deep = lax.broadcasted_iota(jnp.int32, (sub, _LANES), 0)
    eye = ((wide % sub == deep) & (wide < C)).astype(_F32)
    block_of = wide[:_SUBLANES] // sub
    narrow = lax.broadcasted_iota(jnp.int32, (_SUBLANES, C), 1)

    def one_chunk(c, carry):
        if snapshot:
            @pl.when(t * chunks + c == cap_ref[0])
            def _the_chunk_of_capture():
                for h in range(heads):
                    snap_ref[0, h] = s_scr[h].T

        rows = pl.ds(pl.multiple_of(c * C, C), C)
        g_folded = g_ref[0, rows, :]
        q, k = by_head(q_ref[0, rows, :], dk), by_head(k_ref[0, rows, :], dk)
        v = by_head(v_ref[0, rows, :], dv)
        beta = by_head(jnp.dot(b_ref[0, rows, :], to_lanes, precision=hi,
                               preferred_element_type=_F32), _LANES)
        both = jnp.dot(ones_both, g_folded, precision=hi,
                       preferred_element_type=_F32)
        G, inner = by_head(both[:C], dk), by_head(both[C:], dk)
        k_scr[...], g_scr[...], in_scr[...] = k, G, inner
        last = g_scr[:, C - 1:C, :]                               # G_C
        grown, whole = jnp.exp(inner), jnp.exp(G)
        kg, qg = k * grown, q * grown
        k_beta = across(beta, dk) * k

        # the diagonal blocks, pairwise: column b of sub-chunk s against
        # the eight rows of its tile `part` (tiles wholly above the
        # diagonal are never formed), summed along the lanes where it
        # is formed: (heads, 8, 1), entries (part * 8 + r, b) of beta A
        # (strictly below the diagonal) and of B
        sums_a, sums_b = {}, {}
        for s in range(n):
            for b in range(sub):
                j = s * sub + b
                inner_b = in_scr[:, j:j + 1, :]
                k_b = k_scr[:, j:j + 1, :]
                for part in range(b // _SUBLANES, per):
                    top = s * sub + part * _SUBLANES
                    tile = slice(top, top + _SUBLANES)
                    first = b - part * _SUBLANES   # > 0: the diagonal's tile
                    gap = inner[:, tile] - inner_b
                    if first > 0:
                        gap = jnp.where(sublane >= first, gap, -jnp.inf)
                    pair = jnp.exp(gap) * k_b
                    below = jnp.sum(pair * k_beta[:, tile], axis=-1,
                                    keepdims=True)
                    sums_a[s, b, part] = below if first < 0 else jnp.where(
                        sublane > first, below, 0.0)
                    sums_b[s, b, part] = jnp.sum(pair * q[:, tile], axis=-1,
                                                 keepdims=True)

        def placed(sums, where, width):
            """(heads, 8, width): `sums[i]` in the lanes where ``where
            == i``, 0 elsewhere."""
            tile = jnp.zeros((heads, _SUBLANES, width), _F32)
            for i, column in sums:
                tile = jnp.where(where == i, column, tile)
            return tile

        # the blocks below the diagonal, factored around R
        low_off, b_off = [None], [None]
        for s in range(1, n):
            before = s * sub
            r_s = g_scr[:, before - 1:before, :]
            k_dec = rows_of([
                k[:, :before] * jnp.exp(r_s - G[:, :before]),
                jnp.zeros((heads, C - before, dk), _F32)])
            at = slice(before, before + sub)
            off = mm(rows_of([kg[:, at], qg[:, at]]), k_dec, nt)
            low_off.append(beta[:, at, :C] * off[:, :sub])
            b_off.append(off[:, sub:])

        # (I + diag)^-1 of every sub-chunk at once, lanes (s, column):
        # row j is final after j steps and leaves the rows below it,
        # each by column j of its block (strictly below the diagonal)
        # along that block's lanes
        inv = eye
        for j in range(sub - 1):
            column = rows_of([placed(
                [(s, sums_a[s, j, part]) for s in range(n)
                 if (s, j, part) in sums_a], block_of, _LANES)
                for part in range(per)])                # (heads, sub, 128)
            inv = inv - column * inv[..., j:j + 1, :]
        # (lanes from C on are zero)

        state = s_scr[...]                                # (heads, dv, dk)
        from_state = mm(rows_of([k * whole, q * whole]), state, nt)
        rhs = across(beta, dv) * (v - from_state[:, :C])
        U = _solve_in_kernel(rhs, low_off, inv, wide, sub, exact, rows_of)

        Bm = rows_of([
            placed([(s * sub + b, sums_b[s, b, part]) for b in range(sub)
                    if (s, b, part) in sums_b], narrow, C)
            + (0.0 if b_off[s] is None else
               b_off[s][:, part * _SUBLANES:(part + 1) * _SUBLANES])
            for s in range(n) for part in range(per)])     # (heads, C, C)
        o = from_state[:, C:] + mm(Bm, U)
        for h in range(heads):
            o_ref[0, rows, h * dv:(h + 1) * dv] = o[h]
        s_scr[...] = state * jnp.exp(last) + mm(
            jnp.stack([U[h].T for h in range(heads)]),
            k * jnp.exp(last - G))
        return carry

    lax.fori_loop(0, chunks, one_chunk, 0)

    @pl.when(t == pl.num_programs(2) - 1)
    def _last_chunks():
        for h in range(heads):
            st_ref[0, h] = s_scr[h].T


def _call(q, k, v, g, beta, state, cap_chunk, *, chunk: int, chunks: int,
          sub: int, dtype, interpret: bool, head_gate: bool = False):
    """A kernel on folded operands: q, k (B, T, H x dk), v (B, T, H x
    dv), T a multiple of `chunks` chunks; state (B, H, dk, dv) float32;
    cap_chunk: a traced chunk index or None.  A decay a channel
    (`_kernel`): g (B, T, H x dk), beta (B, T, H).  With `head_gate`,
    ONE decay a head (`_head_gate_kernel`): g and beta (B, T, H'), every
    head's, H' the heads filled to whole lanes.  Returns (o (B, T, H x
    dv), the state after T, the state BEFORE chunk `cap_chunk` or
    None)."""
    from jax.experimental.pallas import tpu as pltpu

    B, T = beta.shape[:2]
    _, H, dk, dv = state.shape
    step = chunks * chunk
    kernel, name, most, vmem = (
        (_head_gate_kernel, scopes.DELTA_CHUNK, _HEADS_A_GATE_STEP,
         _HEAD_GATE_VMEM) if head_gate
        else (_kernel, scopes.KDA_CHUNK, _HEADS_A_STEP, None))
    heads = next(n for n in range(min(most, H), 0, -1) if H % n == 0)
    snapshot = cap_chunk is not None

    def time_block(d):
        return pl.BlockSpec((1, step, heads * d),
                            lambda b, h, t, cap: (b, t, h))

    every_head = pl.BlockSpec((1, step, beta.shape[-1]),
                              lambda b, h, t, cap: (b, t, 0))
    state_block = pl.BlockSpec((1, heads, dk, dv),
                               lambda b, h, t, cap: (b, h, 0, 0))
    states = jax.ShapeDtypeStruct((B, H, dk, dv), _F32)
    out_shape = [jax.ShapeDtypeStruct((B, T, H * dv), _F32), states]
    out_specs = [time_block(dv), state_block]
    if snapshot:
        out_shape.append(states)
        out_specs.append(state_block)
    cap = jnp.reshape(jnp.asarray(cap_chunk if snapshot else -1, jnp.int32),
                      (1,))
    outs = pl.pallas_call(
        functools.partial(kernel, chunk=chunk, sub=sub, dk=dk, dv=dv,
                          heads=heads, chunks=chunks, dtype=dtype,
                          snapshot=snapshot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // heads, T // step),
            in_specs=[time_block(dk), time_block(dk), time_block(dv),
                      every_head if head_gate else time_block(dk),
                      every_head, state_block],
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)]
            + [pltpu.VMEM((heads, chunk, dk), _F32)] * (0 if head_gate
                                                        else 3)),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name=name,
    )(cap, q, k, v, g, beta, state)
    return outs[0], outs[1], (outs[2] if snapshot else None)


def _head_gate_kernel(cap_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref,
                      o_ref, st_ref, *rest, chunk: int, sub: int, dk: int,
                      dv: int, heads: int, chunks: int, dtype,
                      snapshot: bool):
    """`_kernel`'s grid step for ONE decay a head: q, k (1, chunks x
    chunk, heads x dk) and v, o (.., heads x dv), heads padded to whole
    lanes; g and b (1, chunks x chunk, H'), every head's log-decay and
    beta a token, H' the heads padded to whole lanes; s0, st, snap (1,
    heads, dk, dv).  Scratch: the heads' states held transposed (heads,
    dv, dk), as `_kernel` holds them.

    `_chunk`'s algebra on a chunk with `_scalar_gate_chunked`'s ``A``
    and ``B``: ``G`` is a number a token, so it is spread along a head's
    lanes once (a product with 0/1), its transpose gives ``G_j`` along
    the lanes to the bit, and ONE (C, C) mask ``exp(G_i - G_j)`` a head
    (every exponent <= 0) turns ``[K; Q] K^T``, one product, into ``A``
    and ``B``: no pairwise decay a channel, no sub-chunk walk, no
    factoring around ``R``.  The solve, the state's part and the
    state's update are `_kernel`'s; the group's heads go side by side
    on a leading axis for the same reason."""
    s_scr, = rest[-1:]
    snap_ref = rest[0] if snapshot else None
    group, t = pl.program_id(1), pl.program_id(2)
    C, n = chunk, chunk // sub
    hi, nt = lax.Precision.HIGHEST, _NT
    mm, exact, by_head, across, rows_of = _kernel_words(heads, dtype)

    def to_a_tile(a):           # (.., C, d) -> (.., 128, d), zeros below
        return a if C == _LANES else jnp.concatenate(
            [a, jnp.zeros((*a.shape[:-2], _LANES - C, a.shape[-1]), _F32)],
            axis=-2)

    @pl.when(t == 0)
    def _first_chunks():
        for h in range(heads):
            s_scr[h] = s0_ref[0, h].T
            if snapshot:
                snap_ref[0, h] = s0_ref[0, h]

    # G as a product with ones below the diagonal (float32, highest
    # precision), and a head's column of it and of beta along that
    # head's lanes
    ones_below = (lax.broadcasted_iota(jnp.int32, (C, C), 1)
                  <= lax.broadcasted_iota(jnp.int32, (C, C), 0)
                  ).astype(_F32)
    padded = b_ref.shape[-1]
    to_lanes = (lax.broadcasted_iota(jnp.int32, (padded, heads * _LANES), 0)
                == group * heads + lax.broadcasted_iota(
                    jnp.int32, (padded, heads * _LANES), 1) // _LANES
                ).astype(_F32)
    row = lax.broadcasted_iota(jnp.int32, (C, _LANES), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, _LANES), 1)
    wide = lax.broadcasted_iota(jnp.int32, (sub, _LANES), 1)
    deep = lax.broadcasted_iota(jnp.int32, (sub, _LANES), 0)
    eye = ((wide % sub == deep) & (wide < C)).astype(_F32)

    def one_chunk(c, carry):
        if snapshot:
            @pl.when(t * chunks + c == cap_ref[0])
            def _the_chunk_of_capture():
                for h in range(heads):
                    snap_ref[0, h] = s_scr[h].T

        rows = pl.ds(pl.multiple_of(c * C, C), C)
        q, k = by_head(q_ref[0, rows, :], dk), by_head(k_ref[0, rows, :], dk)
        v = by_head(v_ref[0, rows, :], dv)
        spread = jnp.dot(jnp.concatenate([
            jnp.dot(ones_below, g_ref[0, rows, :], precision=hi,
                    preferred_element_type=_F32),
            b_ref[0, rows, :]], axis=0), to_lanes, precision=hi,
            preferred_element_type=_F32)
        G, beta = by_head(spread[:C], _LANES), by_head(spread[C:], _LANES)
        # G_j along the lanes: the same numbers, transposed
        G_j = jnp.stack([to_a_tile(G[h]).T[:C] for h in range(heads)])
        decay = jnp.exp(jnp.where(col <= row, G - G_j, -jnp.inf))
        both = mm(rows_of([k, q]), to_a_tile(k), nt)   # (heads, 2C, 128)
        low = beta * jnp.where(col < row, both[:, :C] * decay, 0.0)
        Bm = both[:, C:] * decay
        # lanes from C on are zero in `low`, `Bm` and `inv`

        # (I + diag)^-1 of every sub-chunk at once, lanes (s, column),
        # as `_kernel` takes it: column j of a block along that block's
        # lanes is a masked sum along the lanes
        diag = jnp.zeros((heads, sub, _LANES), _F32)
        for s in range(n):
            diag = jnp.where(wide // sub == s,
                             low[:, s * sub:(s + 1) * sub], diag)
        inv = eye
        for j in range(sub - 1):
            column = jnp.zeros((heads, sub, _LANES), _F32)
            for s in range(n):
                column = jnp.where(wide // sub == s, jnp.sum(
                    jnp.where(wide == s * sub + j, diag, 0.0), axis=-1,
                    keepdims=True), column)
            inv = inv - column * inv[..., j:j + 1, :]

        state = s_scr[...]                                # (heads, dv, dk)
        whole = jnp.exp(G)
        from_state = mm(rows_of([k * whole, q * whole]), state, nt)
        rhs = across(beta, dv) * (v - from_state[:, :C])
        U = _solve_in_kernel(rhs, [None] + [
            jnp.where(wide < s * sub, low[:, s * sub:(s + 1) * sub],
                      0.0)[..., :C] for s in range(1, n)],
            inv, wide, sub, exact, rows_of)

        o = from_state[:, C:] + mm(Bm, to_a_tile(U))
        for h in range(heads):
            o_ref[0, rows, h * dv:(h + 1) * dv] = o[h]
        last = G[:, C - 1:C]                                      # G_C
        s_scr[...] = state * jnp.exp(last) + mm(
            jnp.stack([U[h].T for h in range(heads)]),
            k * jnp.exp(last - G))
        return carry

    lax.fori_loop(0, chunks, one_chunk, 0)

    @pl.when(t == pl.num_programs(2) - 1)
    def _last_chunks():
        for h in range(heads):
            st_ref[0, h] = s_scr[h].T


def _fits_the_kernel(k, v, g, chunk: int, sub: int) -> bool:
    """A decay a channel, whole lanes a head, more than one column,
    sub-chunks of whole sublane tiles and a chunk whose columns fit a
    tile's lanes."""
    return (g.shape[-1] == k.shape[-1]
            and k.shape[1] > 1 and k.shape[-1] % _LANES == 0
            and v.shape[-1] % _LANES == 0 and sub % _SUBLANES == 0
            and chunk % sub == 0 and chunk <= _LANES)


def _to_lanes(d: int) -> int:
    """`d` filled to whole lanes."""
    return -(-d // _LANES) * _LANES


def _fits_the_head_gate_kernel(k, v, g, chunk: int, sub: int) -> bool:
    """ONE decay a head, more than one column, sub-chunks of whole
    sublane tiles, a chunk whose columns fit a tile's lanes, and heads
    that fill at least half of the whole lanes they are padded to
    (keys of 96 in 128, values of 192 in 256; heads of 16 do not)."""
    dk, dv = k.shape[-1], v.shape[-1]
    return (g.shape[-1] == 1 < dk and k.shape[1] > 1
            and 2 * dk >= _to_lanes(dk) and 2 * dv >= _to_lanes(dv)
            and sub % _SUBLANES == 0 and chunk % sub == 0
            and chunk <= _LANES)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _kernel_form(q, k, v, g, beta, state, capture, chunk, sub, dtype,
                 interpret):
    """`kda_chunk` on operands as `kda_chunked` takes them; state (B, H,
    dk, dv) float32, capture int32 or None."""
    B, T, H, dk = k.shape
    chunks = min(_CHUNKS_A_STEP, -(-T // chunk))
    fill = -T % (chunks * chunk)
    q, k, v, g, beta = (a.astype(_F32) for a in (q, k, v, g, beta))
    if fill:        # identity steps at the end, as `kda_chunked` fills
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    at = None if capture is None else capture // chunk
    with jax.named_scope(scopes.ATTN_LINEAR):
        o, state, before = _call(
            *(a.reshape(B, T + fill, -1) for a in (q, k, v, g)), beta,
            state, at, chunk=chunk, chunks=chunks, sub=sub, dtype=dtype,
            interpret=interpret)
        o = o.reshape(B, T + fill, H, -1)[:, :T]
        if capture is None:
            return o, state, None
        # the one chunk that holds `capture`, again, in `_chunk`'s own
        # words, from the state the kernel handed back at its start
        one = (jnp.swapaxes(lax.dynamic_slice_in_dim(
            a, at * chunk, chunk, axis=1), 1, 2)
            for a in (q, k, v, g, beta))
        snap = _chunk(*one, before, sub, dtype, capture - at * chunk)[2]
    return o, state, snap


def _jnp_form_fwd(q, k, v, g, beta, state, capture, chunk, sub, dtype,
              interpret):
    # a differentiated program runs the `jnp` form, forward and backward
    out, vjp = jax.vjp(
        lambda *a: kda_chunked(*a, chunk=chunk, sub=sub, dtype=dtype,
                               capture=capture), q, k, v, g, beta, state)
    return out, (vjp, capture)


def _jnp_form_bwd(chunk, sub, dtype, interpret, res, cts):
    vjp, capture = res
    no_grad = None if capture is None else np.zeros(
        np.shape(capture), jax.dtypes.float0)
    return (*vjp(cts), no_grad)


_kernel_form.defvjp(_jnp_form_fwd, _jnp_form_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def _head_gate_form(q, k, v, g, beta, state, capture, chunk, sub, dtype,
                    interpret):
    """`_kernel_form` for ONE decay a head, g (B, T, H, 1): the heads
    are filled to whole lanes on the way in (zeros: a filled channel of
    k leaves its row of the state zero, one of q reads nothing, one of
    v writes zeros) and cut back on the way out; the state comes and
    goes (B, H, dk, dv) as the cache stores it."""
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    chunks = min(_CHUNKS_A_STEP, -(-T // chunk))
    fill = -T % (chunks * chunk)
    q, k, v, g, beta = (jnp.pad(     # identity steps at the end
        a.astype(_F32), ((0, 0), (0, fill)) + ((0, 0),) * (a.ndim - 2))
        for a in (q, k, v, g, beta))
    at = None if capture is None else capture // chunk

    def lanes(a, axis=-1):      # zeros up to whole lanes along `axis`
        room = [(0, 0)] * a.ndim
        room[axis] = (0, _to_lanes(a.shape[axis]) - a.shape[axis])
        return jnp.pad(a, room)

    with jax.named_scope(scopes.ATTN_LINEAR):
        o, after, before = _call(
            *(lanes(a).reshape(B, T + fill, -1) for a in (q, k, v)),
            lanes(g[..., 0]), lanes(beta), lanes(lanes(state), -2), at,
            chunk=chunk, chunks=chunks, sub=sub, dtype=dtype,
            interpret=interpret, head_gate=True)
        o = o.reshape(B, T + fill, H, -1)[:, :T, :, :dv]
        after = after[:, :, :dk, :dv]
        if capture is None:
            return o, after, None
        # the one chunk that holds `capture`, again, in the `jnp` form's
        # own words, from the state the kernel handed back at its start
        one = (lax.dynamic_slice_in_dim(a, at * chunk, chunk, axis=1)
               for a in (q, k, v, g, beta))
        snap = _scalar_gate_chunked(
            *one, before[:, :, :dk, :dv], chunk, sub, dtype,
            capture - at * chunk)[2]
    return o, after, snap


_head_gate_form.defvjp(_jnp_form_fwd, _jnp_form_bwd)


def _kernel_form_for(k, v, g, chunk: int, sub: int):
    """The kernel form whose body takes these shapes, by ``g``'s
    trailing size, or None."""
    if _fits_the_kernel(k, v, g, chunk, sub):
        return _kernel_form
    if _fits_the_head_gate_kernel(k, v, g, chunk, sub):
        return _head_gate_form
    return None


@functools.partial(jax.jit, static_argnames=("chunk", "sub", "dtype",
                                             "interpret"))
def kda_chunk(q, k, v, g, beta, state=None, *, chunk: int = 64,
              sub: int = 16, dtype=jnp.bfloat16, capture=None,
              interpret: bool = False
              ) -> Tuple[jnp.ndarray, jnp.ndarray, Optional[jnp.ndarray]]:
    """`kda_chunked`'s contract as one Pallas call (module docstring),
    by ``g``'s trailing size: a decay a channel is the call named
    ``kda_chunk`` (head sizes whole lanes, multiples of 128), ONE decay
    a head the call named ``delta_chunk`` (heads that fill at least
    half of the lanes they are padded to).  `sub` whole sublane tiles
    (a multiple of 8) and `chunk` a multiple of `sub`, at most 128.
    ``interpret=True`` runs the kernel in the Pallas interpreter (the
    CPU tests).  Jitted: the layers of one program share one trace and
    one lowering of the kernel.  Differentiated, it is `kda_chunked`,
    forward and backward."""
    sub = min(sub, chunk)
    form = _kernel_form_for(k, v, g, chunk, sub)
    if form is None:
        raise ValueError(
            f"kda_chunk: heads of {k.shape[-1]} x {v.shape[-1]}, a decay "
            f"of {g.shape[-1]}, chunk {chunk}, sub {sub} and {k.shape[1]} "
            "columns do not fit the kernel")
    B, _, H, dk = k.shape
    if state is None:
        state = jnp.zeros((B, H, dk, v.shape[-1]), _F32)
    if capture is not None:
        capture = jnp.asarray(capture, jnp.int32)
    return form(q, k, v, g, beta, state.astype(_F32), capture, chunk, sub,
                jnp.dtype(dtype), interpret)


def kda_prefill(q, k, v, g, beta, state=None, *, chunk: int = 64,
                sub: int = 16, dtype=jnp.bfloat16, capture=None):
    """A prefill's delta rule by the form that fits what the program
    can see: on the chip `kda_chunk`, in the body ``g``'s trailing size
    names, where the shapes fit that body; `kda_chunked` everywhere
    else (module docstring)."""
    on_chip = jax.default_backend() == "tpu" and _kernel_form_for(
        k, v, g, chunk, min(sub, chunk)) is not None
    form = kda_chunk if on_chip else kda_chunked
    return form(q, k, v, g, beta, state, chunk=chunk, sub=sub, dtype=dtype,
                capture=capture)


# ---------------------------------------------------------------------------
# a decode wave's kernel
# ---------------------------------------------------------------------------

def _step_on_layer(q, k, v, g, beta, stack, j):
    """`kda_step` on layer `j` of the stack (n, B, H, dk, dv), set back:
    the `jnp` form of `kda_decode`."""
    with jax.named_scope(scopes.LINEAR_STATE):
        state = lax.dynamic_index_in_dim(stack, j, 0, keepdims=False)
    o, new = kda_step(q, k, v, g, beta, state)
    with jax.named_scope(scopes.LINEAR_STATE):
        return o, lax.dynamic_update_index_in_dim(
            stack, new.astype(stack.dtype), j, 0)


def _step_kernel(j_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref,
                 o_ref, st_ref, pick_ref):
    """One (row, group of eight heads) grid step.  q, k, g (1, H, dk)
    and v, o (1, H, dv): the row's heads on the sublanes, resident while
    the row's groups go by; b (1, 1, H); s, st (1, 1, 8, dk, dv): the
    group's matrices of layer ``j_ref[0]``, the same block of the same
    buffer in and out.  Scratch `pick` (24, 128, dv) bfloat16: the 0/1
    selectors below, made at the first grid step.

    `kda_step`'s operations in its order, float32.  A matrix lies with
    ``dk`` on the sublanes, so the sums over ``dk`` are vreg adds and
    one sublane reduction each and ``delta`` is a row, spread along the
    sublanes for free; what costs is ``k``, ``q`` and ``exp(g)`` as
    COLUMNS spread along the lanes, 48 vregs a head (module
    docstring).  The MXU makes them, exactly: the group's 24 rows are
    split into three bfloat16 parts each (their sum is the float32
    value to the bit), the 72 part rows are transposed as one (128,
    dk) tile, and a head's column spread over ``dv`` lanes is that tile
    times a 0/1 matrix that picks its three parts: products with 1 and
    a float32 sum of three terms, no rounding anywhere."""
    heads = _HEADS_A_WAVE_STEP
    group = pl.program_id(1)
    H, dk = q_ref.shape[1:]
    dv = v_ref.shape[-1]
    bf16 = jnp.bfloat16

    @pl.when((pl.program_id(0) == 0) & (group == 0))
    def _the_selectors():
        part_row = lax.broadcasted_iota(jnp.int32, (_LANES, dv), 0)
        for column in range(3 * heads):
            pick_ref[column] = ((part_row % (3 * heads) == column)
                                & (part_row < 9 * heads)).astype(bf16)

    rows = pl.ds(pl.multiple_of(group * heads, heads), heads)
    v = v_ref[0, rows, :]
    # beta of the group's heads as a column: the row masked to one head
    # a sublane, summed along the lanes
    own = (lax.broadcasted_iota(jnp.int32, (heads, H), 1)
           == group * heads + lax.broadcasted_iota(jnp.int32, (heads, H), 0))
    beta = jnp.sum(jnp.where(own, b_ref[0], 0.0), axis=-1, keepdims=True)
    whole = jnp.concatenate([k_ref[0, rows, :], q_ref[0, rows, :],
                             jnp.exp(g_ref[0, rows, :])], axis=0)
    high = whole.astype(bf16).astype(_F32)
    middle = (whole - high).astype(bf16).astype(_F32)
    low = (whole - high - middle).astype(bf16).astype(_F32)
    parts = jnp.concatenate(
        [high, middle, low,
         jnp.zeros((_LANES - 9 * heads, dk), _F32)], axis=0).T.astype(bf16)

    def spread(which, h):       # column (which, h) along dv lanes
        return jnp.dot(parts, pick_ref[which * heads + h],
                       preferred_element_type=_F32)

    out = []
    for h in range(heads):
        k_h = spread(0, h)
        decayed = s_ref[0, 0, h] * spread(2, h)
        seen = jnp.sum(decayed * k_h, axis=0, keepdims=True)   # S'^T k
        delta = beta[h:h + 1] * (v[h:h + 1] - seen)
        new = decayed + k_h * delta
        st_ref[0, 0, h] = new
        out.append(jnp.sum(new * spread(1, h), axis=0, keepdims=True))
    o_ref[0, rows, :] = jnp.concatenate(out, axis=0)


def _fits_the_step_kernel(stack, g) -> bool:
    """float32 matrices, a decay a channel, whole lanes a head both
    ways, heads in whole groups."""
    _, _, H, dk, dv = stack.shape
    return (stack.dtype == _F32 and g.shape[-1] == dk
            and dk % _LANES == 0 and dv % _LANES == 0
            and H % _HEADS_A_WAVE_STEP == 0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _step_kernel_form(q, k, v, g, beta, stack, j, interpret):
    """The kernel on q, k, g (B, H, dk), v (B, H, dv), beta (B, H),
    float32, the stack (n, B, H, dk, dv) and j int32 ()."""
    from jax.experimental.pallas import tpu as pltpu

    _, B, H, dk, dv = stack.shape
    heads = _HEADS_A_WAVE_STEP

    def row(d):     # a row's heads: fetched once a row, not once a group
        return pl.BlockSpec((1, H, d), lambda b, h, j: (b, 0, 0))

    matrices = pl.BlockSpec((1, 1, heads, dk, dv),
                            lambda b, h, j: (j[0], b, h, 0, 0))
    with jax.named_scope(scopes.ATTN_LINEAR):
        o, stack = pl.pallas_call(
            _step_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, H // heads),
                in_specs=[row(dk), row(dk), row(dv), row(dk),
                          pl.BlockSpec((1, 1, H), lambda b, h, j: (b, 0, 0)),
                          matrices],
                out_specs=[row(dv), matrices],
                scratch_shapes=[
                    pltpu.VMEM((3 * heads, _LANES, dv), jnp.bfloat16)]),
            out_shape=[jax.ShapeDtypeStruct((B, H, dv), _F32),
                       jax.ShapeDtypeStruct(stack.shape, _F32)],
            # the stack is the result: only layer j's blocks are fetched
            # and written, the other layers' bytes are not touched
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
            name=scopes.KDA_DECODE,
        )(jnp.reshape(j, (1,)), q, k, v, g, beta.reshape(B, 1, H), stack)
    return o, stack


def _step_jnp_fwd(q, k, v, g, beta, stack, j, interpret):
    # a differentiated program runs the `jnp` form, forward and backward
    out, vjp = jax.vjp(lambda *a: _step_on_layer(*a, j),
                       q, k, v, g, beta, stack)
    return out, vjp


def _step_jnp_bwd(interpret, vjp, cts):
    return (*vjp(cts), np.zeros((), jax.dtypes.float0))


_step_kernel_form.defvjp(_step_jnp_fwd, _step_jnp_bwd)


#: jitted: the KDA layers of one program share one trace and one
#: lowering of the kernel
_step_kernel_call = jax.jit(_step_kernel_form, static_argnums=(7,))


# ... with ONE decay a head

#: a grid step's matrices at most, in bytes as they lie (a row's thirty
#: of 96 x 192 are 96 x 256 lanes each, 2.95 MB: one step a row, and
#: the wave's own operands go in as they are), and the VMEM the kernel
#: may take: the block in and out, each buffered twice.  What the chip
#: said (my chip runs, PR 61; 32 rows of 30 heads, one layer of six,
#: donated; `kda_step` on the layer 0.576 ms, a kernel that only copies
#: the blocks 0.326-0.335): 0.321-0.339 ms a call whether a step holds
#: 5, 6, 10, 15 or 30 heads, and with a head's arithmetic done four
#: times over still 0.327: the copies are all it costs
_GATE_WAVE_BLOCK = 3 * 2 ** 20
_GATE_WAVE_VMEM = 32 * 2 ** 20


def _head_gate_step_kernel(j_ref, q_ref, k_ref, v_ref, g_ref, b_ref, s_ref,
                           o_ref, st_ref, rows_ref):
    """One (row, group of heads) grid step for ONE decay a head.  q, k
    (1, 1, heads, dk), v, o (1, 1, heads, dv), g, b (1, 1, heads, 1):
    the group's heads on the sublanes; s, st (1, 1, heads, dk, dv): the
    group's matrices of layer ``j_ref[0]``, the same block of the same
    buffer in and out.  Scratch `rows` (2, heads, dv): a head's decay
    and its beta along the lanes.

    `kda_step`'s operations in its order, float32.  The decay is a
    number a head, so it multiplies a matrix as a row spread along the
    sublanes, as ``delta`` does.  ``k`` and ``q`` are needed as COLUMNS:
    the group's rows are transposed on the MXU (the identity times
    their transpose at the highest precision: a float32 value's three
    bfloat16 parts times 1, summed to the value to the bit), and a
    head's column spread along the lanes is twelve lane broadcasts,
    hidden under the matrices' copies.  A matrix of 192 lanes lies as a
    tile and a half, the second tile's upper half masked in every
    operation."""
    heads, dk = k_ref.shape[2:]
    dv = v_ref.shape[-1]
    eye = (lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == lax.broadcasted_iota(jnp.int32, (dk, dk), 1)).astype(_F32)

    def columns(rows):          # (heads, dk) -> (dk, heads)
        return lax.dot_general(eye, rows, (((1,), (1,)), ((), ())),
                               precision=lax.Precision.HIGHEST,
                               preferred_element_type=_F32)

    k, q, v = columns(k_ref[0, 0]), columns(q_ref[0, 0]), v_ref[0, 0]
    # (through VMEM: a number spread along lanes AND sublanes at once is
    # a broadcast the compiler does not have)
    rows_ref[0] = jnp.broadcast_to(jnp.exp(g_ref[0, 0]), (heads, dv))
    rows_ref[1] = jnp.broadcast_to(b_ref[0, 0], (heads, dv))
    out = []
    for h in range(heads):
        k_h = k[:, h:h + 1]
        decayed = s_ref[0, 0, h] * rows_ref[0, h:h + 1, :]
        seen = jnp.sum(decayed * k_h, axis=0, keepdims=True)   # S'^T k
        delta = rows_ref[1, h:h + 1, :] * (v[h:h + 1] - seen)
        new = decayed + k_h * delta
        st_ref[0, 0, h] = new
        out.append(jnp.sum(new * q[:, h:h + 1], axis=0, keepdims=True))
    o_ref[0, 0] = jnp.concatenate(out, axis=0)


def _fits_the_head_gate_step_kernel(stack, g) -> bool:
    """float32 matrices, ONE decay a head, keys of whole sublane tiles
    and values that fill at least half of the lanes they lie in (96 x
    192: twelve tiles by 192 of 256 lanes; heads of 12 x 24 do not)."""
    _, _, _, dk, dv = stack.shape
    return (stack.dtype == _F32 and g.shape[-1] == 1 < dk
            and dk % _SUBLANES == 0 and 2 * dv >= _to_lanes(dv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def _head_gate_step_form(q, k, v, g, beta, stack, j, interpret):
    """`_step_kernel_form` for ONE decay a head, g (B, H, 1).  The
    heads go in the largest groups whose matrices fit a grid step's
    bytes, a whole row where thirty do: the matrices' head axis is a
    leading axis of the stack, so a group is a block index there, and
    the wave's own operands are handed in a group a block (as they are,
    where a group is a row): nothing is sliced at sublanes that are no
    tile's first."""
    from jax.experimental.pallas import tpu as pltpu

    _, B, H, dk, dv = stack.shape
    most = max(1, _GATE_WAVE_BLOCK // (dk * _to_lanes(dv) * 4))
    heads = next(n for n in range(min(most, H), 0, -1) if H % n == 0)

    def grouped(a):             # (B, H, d) -> (B, groups, heads, d)
        return a.reshape(B, H // heads, heads, a.shape[-1])

    def group(d):               # a (row, group)'s own, whole
        return pl.BlockSpec((1, 1, heads, d), lambda b, h, j: (b, h, 0, 0))

    matrices = pl.BlockSpec((1, 1, heads, dk, dv),
                            lambda b, h, j: (j[0], b, h, 0, 0))
    with jax.named_scope(scopes.ATTN_LINEAR):
        o, stack = pl.pallas_call(
            _head_gate_step_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, H // heads),
                in_specs=[group(dk), group(dk), group(dv), group(1),
                          group(1), matrices],
                out_specs=[group(dv), matrices],
                scratch_shapes=[pltpu.VMEM((2, heads, dv), _F32)]),
            out_shape=[jax.ShapeDtypeStruct((B, H // heads, heads, dv), _F32),
                       jax.ShapeDtypeStruct(stack.shape, _F32)],
            # the stack is the result, as `_step_kernel_form`'s is
            input_output_aliases={6: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_GATE_WAVE_VMEM),
            interpret=interpret,
            name=scopes.DELTA_DECODE,
        )(jnp.reshape(j, (1,)), *(grouped(a) for a in (
            q, k, v, g, beta[..., None])), stack)
    return o.reshape(B, H, dv), stack


_head_gate_step_form.defvjp(_step_jnp_fwd, _step_jnp_bwd)
_head_gate_step_call = jax.jit(_head_gate_step_form, static_argnums=(7,))


def _step_call_for(stack, g):
    """The jitted step kernel whose body takes these shapes, by ``g``'s
    trailing size, or None."""
    if _fits_the_step_kernel(stack, g):
        return _step_kernel_call
    if _fits_the_head_gate_step_kernel(stack, g):
        return _head_gate_step_call
    return None


def kda_decode(q, k, v, g, beta, stack, j, *, interpret: bool = False):
    """A decode wave's delta rule on layer `j` of the KDA layers'
    stacked state.  q, k (B, H, dk); g (B, H, dk) or (B, H, 1): one
    decay a head; v (B, H, dv); beta (B, H); stack (n, B, H, dk, dv)
    float32; j an index into its first axis.  Returns (o (B, H, dv)
    float32, the stack with layer `j` one token on and every other
    layer as it was).

    By the form that fits what the program can see, ONE Pallas call on
    the chip either way (module docstring): with a decay a channel,
    where a head is whole lanes and the heads whole groups of eight,
    the call named ``kda_decode``; with ONE decay a head (``g`` of
    trailing size 1), keys of whole sublane tiles and values that fill
    half their lanes, the call named ``delta_decode``; else `kda_step`
    on the layer indexed out and set back: the CPU, heads of 16, a
    state that is not float32.  ``interpret=True`` runs the kernel in
    the Pallas interpreter where the shapes fit one (the CPU tests).
    Differentiated, it is the `jnp` form, forward and backward."""
    j = jnp.asarray(j, jnp.int32)
    call = _step_call_for(stack, g) if (
        interpret or jax.default_backend() == "tpu") else None
    if call is None:
        return _step_on_layer(q, k, v, g, beta, stack, j)
    return call(*(a.astype(_F32) for a in (q, k, v, g, beta)), stack, j,
                interpret)
