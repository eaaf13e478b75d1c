"""One decode column of grouped-query attention over each row's RING,
read where it lies in the window layers' stacked rings.

A window layer keeps, per cache slot, a ring of its last ``window`` K/V
rows (models/laguna_decode.py: the row of cache slot ``s`` is ``s mod
window``; K and V of one token folded into ``n_kv_head * hd`` lanes, a
K/V head a lane slice).  After this column's row is written at ``pos
mod window``, ring row ``r`` holds slot ``pos - ((pos - r) mod
window)`` and is attended iff that is ``>= start``:

  ``s[h, r] = q[h] . k[r, h // G] * scale``
  ``o[h] = sum_r softmax(s[h])[r] v[r, h // G]``

which is ``attend_rows(q, wk[j], wv[j], _ring_mask(pos, start, window))``
of models/laguna_decode.py, the ``jnp`` form the CPU runs and the
kernel is held to.  `ring_decode` is one ``pallas_call`` named
``ring_decode``: both stacks ``(n_window, B, window, width)`` stay in
HBM, WHOLE; the layer is a prefetched scalar in the rings' index map
(traced inside a ``lax.scan`` or a Python int: one signature), so no
ring is sliced out of the stack first.  A grid step is one row: its
ring of keys and its ring of values, one contiguous region each, come
into VMEM through Pallas's own double buffering while the row before
is attended, and for each K/V head the group's scores, their maximum
and sum and the weighted sum are taken from the head's lanes of that
buffer (ops/gqa_paged_decode.py's body without the block table: a
group of query heads is padded to a whole sublane tile).  A ring
crosses HBM once.

The kernel reads and writes nothing else: this column's row is in the
ring already (the step's ``.at[j, rows, pos % window].set(mode="drop")``
before the call, which leaves a row with ``pos == 0`` as it is), so the
stacks are operands alone and alias nothing.

Precision: operands as stored (bf16), scores, maximum and sum float32,
the probabilities normalised in float32 and cast to the rings' dtype
before the weighted sum, which accumulates in float32: `attend_rows`'
operations in its order.  Every one of the ``min(context, window)``
keys is attended, nothing approximated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

_LANES = 128
#: a K/V head's group of query heads is padded to whole sublane tiles
_SUBLANES = 8
#: rows of a packed bf16 tile: a window is whole tiles of them
_ROWS_A_TILE = 16
_MASKED = -1e30


def fits_the_kernel(q, wk) -> bool:
    """Heads and folded rows of whole lanes, a window of whole sublane
    tiles: what the kernel reads off its operands' shapes."""
    hd = q.shape[-1]
    window, width = wk.shape[-2:]
    return (hd % _LANES == 0 and width % hd == 0
            and window % _ROWS_A_TILE == 0)


def _kernel(j_ref, pos_ref, start_ref, q_ref, k_ref, v_ref, o_ref, *,
            scale: float):
    """One row.  Prefetched scalars: the layer (1,); pos, start (B,).
    q, o (1, n_kv, Gp, hd): a K/V head's group of query heads, zero
    rows up to a whole sublane tile; k, v (1, 1, window, n_kv * hd):
    the row's rings of layer ``j_ref[0]``."""
    del j_ref                                   # the index maps' alone
    b = pl.program_id(0)
    _, n_kv, Gp, hd = q_ref.shape
    window = k_ref.shape[2]
    f32 = jnp.float32
    dt = k_ref.dtype
    n, lo = pos_ref[b], start_ref[b]
    # row r holds slot n - ((n - r) mod window): the newest at n mod
    # window, the rows after it a lap behind
    at = n % window
    r = lax.broadcasted_iota(jnp.int32, (Gp, window), 1)
    held = n - (at - r) - jnp.where(r > at, window, 0)
    ok = held >= lo
    nt = (((1,), (1,)), ((), ()))                          # a @ b.T
    for g in range(n_kv):
        lanes = pl.ds(g * hd, hd)
        s = lax.dot_general(q_ref[0, g], k_ref[0, 0, :, lanes], nt,
                            preferred_element_type=f32) * scale
        s = jnp.where(ok, s, _MASKED)                      # (Gp, window)
        e = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        o_ref[0, g] = jnp.dot(probs.astype(dt), v_ref[0, 0, :, lanes],
                              preferred_element_type=f32
                              ).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("n_kv_head", "scale", "interpret"))
def ring_decode(q, wk, wv, j, pos, start, *, n_kv_head: int, scale: float,
                interpret: bool = False):
    """q (B, H, hd); wk, wv (n_window, B, window, n_kv_head * hd) the
    whole stacked rings, of which layer `j` (an index, may be traced),
    this column's rows written; pos, start (B,) -> (B, H, hd) in the
    rings' dtype: ``attend_rows`` over ``_ring_mask(pos, start,
    window)`` (module docstring).  ``interpret=True`` runs the kernel
    in the Pallas interpreter (the CPU tests)."""
    from jax.experimental.pallas import tpu as pltpu

    B, H, hd = q.shape
    _, _, window, width = wk.shape
    G = H // n_kv_head
    Gp = -(-G // _SUBLANES) * _SUBLANES
    dt = wk.dtype
    i32 = jnp.int32
    # a K/V head's query heads as a tile of their own: (n_kv, Gp, hd)
    grouped = jnp.pad(q.astype(dt).reshape(B, n_kv_head, G, hd),
                      ((0, 0), (0, 0), (0, Gp - G), (0, 0)))

    def group():
        return pl.BlockSpec((1, n_kv_head, Gp, hd),
                            lambda b, *_: (b, 0, 0, 0))

    def ring():
        return pl.BlockSpec((1, 1, window, width),
                            lambda b, j, *_: (j[0], b, 0, 0))

    with jax.named_scope(scopes.ATTN_WINDOW):
        out = pl.pallas_call(
            functools.partial(_kernel, scale=scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(B,),
                in_specs=[group(), ring(), ring()],
                out_specs=group()),
            out_shape=jax.ShapeDtypeStruct((B, n_kv_head, Gp, hd), dt),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                # two rings twice (this row's, the next on its way in),
                # and room for a head's scores and the rest
                vmem_limit_bytes=4 * window * width * dt.itemsize
                + (8 << 20)),
            interpret=interpret,
            name=scopes.RING_DECODE,
        )(jnp.reshape(jnp.asarray(j, i32), (1,)), pos.astype(i32),
          start.astype(i32), grouped, wk, wv)
    return out[:, :, :G].reshape(B, H, hd)


__all__ = ["ring_decode", "fits_the_kernel"]
