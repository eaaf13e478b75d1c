"""The selective-scan recurrence of a Mamba mixer (Gu & Dao 2023).

  ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t``,  ``y_t = sum_n s_t C_t``

over T columns of one sequence, everything float32.  Two bodies, one
mathematics:

  * `selective_scan_reference` -- pure ``jnp``: `chunk` columns'
    exponentials and outer products in one elementwise pass, then the
    multiply-add chain through them unrolled.  What the CPU runs, what
    a decode step (T = 1: one elementwise expression over every row's
    state, bound by HBM) compiles to, and what the kernel is held to.
  * `selective_scan` -- one ``pallas_call`` named ``ssm_scan``.  XLA
    runs the chain as ~1.3 kernels a column, each a trip of the state
    through HBM; here the state stays in VMEM from the first column to
    the last and the columns are walked inside the kernel.

The kernel's grid is (row, chunk of 128 columns, block of ``d_inner``),
the last two in order.  A block's state ``(d_state, block)`` (8 vregs at
16 x 512) lives in VMEM scratch for the whole row; a grid step takes it
through a chunk's columns, eight at a time: one ``(8, block)`` tile of
``dt`` and ``x``, the eight columns' chain unrolled, each column's
output reduced over ``d_state`` as its state passes and stored as one
row of ``y``.  ``B_t`` and ``C_t`` are needed as ``(d_state, 1)``
columns spread along the lanes; that spread is made once a chunk (at
the chunk's first block, from ``(d_state, 128)`` tiles of B and C
transposed: a lane rotation by the group's offset, then eight static
lane broadcasts) and read back by every block, which is why the blocks
of ``d_inner`` are the inner grid axis and not the outer one.

The same operations in the same order as the reference on every element
of the state: ``exp(dt A)``, ``(dt x) B``, ``a s + bx``.  So a column
with ``dt = 0`` is the identity exactly (``exp(0) = 1``, ``+ 0``), the
state does not depend on where a chunk's edge falls, and padding T up
to a whole chunk adds identity columns.  Only the sum over ``d_state``
may associate otherwise than XLA's.  `capture` (a traced column index,
scalar-prefetched) hands back the state after that column: a select a
column beside the chain, never an operation on it.

The kernel is small on purpose where Python sees it (one body of eight
columns, ~300 equations): a serving engine traces and lowers it once a
prefill bucket at every start, beside its running decode loop, and
`selective_scan` is jitted so that the walks of one program share that.

Differentiable: the backward pass is the reference chain's VJP (no
train cell runs a Mamba layer yet; a backward kernel is its own work).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

#: columns a grid step: the lane width of one (d_state, chunk) tile of
#: B and C, and 2 x 1 MB of VMEM for their columns spread along lanes;
#: T is padded up to it with identity columns
_CHUNK = 128
#: lanes of d_inner a grid step: a (16, 512) float32 state is 8 vregs,
#: with its decay, its input and their product 32 of the core's 64
_BLOCK = 512
#: columns unrolled together: the sublanes of one float32 tile
_GROUP = 8
_LANES = 128


def selective_scan_reference(dt, x, A, Bm, Cm, s0, chunk: int,
                             capture=None):
    """dt, x (B, T, di); A (N, di); Bm, Cm (B, T, N); s0 (B, N, di) ->
    (y (B, T, di), s_T, s after column `capture` or None).

    `chunk` columns at a time: their ``exp`` and outer products in one
    elementwise pass, then the multiply-add chain through them unrolled,
    each column's output reduced over N as its state passes.  The chain
    is the same sequence of operations on ``s`` wherever the chunks'
    edges fall."""
    B, T, di = x.shape
    chunk = min(chunk, T)          # a decode step is one column
    n_chunks = -(-T // chunk)
    tail = n_chunks * chunk - T
    if tail:                       # identity columns at the end
        pad = lambda a: jnp.pad(a, ((0, 0), (0, tail), (0, 0)))  # noqa: E731
        dt, x, Bm, Cm = pad(dt), pad(x), pad(Bm), pad(Cm)

    def chunks(a):                 # (B, T, w) -> (n_chunks, B, chunk, w)
        return a.reshape(B, n_chunks, chunk, a.shape[-1]).swapaxes(0, 1)

    def one(carry, xs):
        s, snap = carry
        dt_c, dtx_c, b_c, c_c, first = xs
        a = jnp.exp(dt_c[:, :, None, :] * A)            # (B, Q, N, di)
        bx = dtx_c[:, :, None, :] * b_c[..., None]
        ys = []
        for q in range(chunk):
            s = a[:, q] * s + bx[:, q]
            # the column's output at once: the chain's states are never
            # stacked (32 of them are 10 MB a chunk at the 3B's width)
            ys.append(jnp.sum(s * c_c[:, q, :, None], axis=1))
            if capture is not None:
                snap = jnp.where(capture == first + q, s, snap)
        y = jnp.stack(ys, axis=1)                       # (B, Q, di)
        return (s, snap), y

    firsts = jnp.arange(n_chunks, dtype=jnp.int32) * chunk
    xs = (chunks(dt), chunks(dt * x), chunks(Bm), chunks(Cm), firsts)
    init = (s0, s0 if capture is not None else ())
    if n_chunks == 1:
        (s, snap), y = one(init, jax.tree.map(lambda a: a[0], xs))
        y = y[None]
    else:
        (s, snap), y = lax.scan(one, init, xs)
    y = y.swapaxes(0, 1).reshape(B, n_chunks * chunk, di)[:, :T]
    return y, s, (snap if capture is not None else None)


# ---------------------------------------------------------------------------
# the kernel
# ---------------------------------------------------------------------------

def _kernel(cap_ref, dt_ref, x_ref, a_ref, bt_ref, ct_ref, s0_ref,
            y_ref, st_ref, *rest, block: int, snapshot: bool):
    """One (row, chunk, block) grid step.  dt, x, y (1, chunk, block);
    a (N, block); bt, ct (1, N, chunk); s0, st, snap (1, N, block);
    scratch: the row's state and snapshot (blocks, N, block), the
    chunk's B and C columns along lanes (chunk, N, 128)."""
    from jax.experimental.pallas import tpu as pltpu

    if snapshot:
        snap_ref, s_scr, bb_scr, cb_scr, snap_scr = rest
    else:
        s_scr, bb_scr, cb_scr = rest
    c, d = pl.program_id(1), pl.program_id(2)
    n_state = a_ref.shape[0]
    groups = _CHUNK // _GROUP

    @pl.when(c == 0)
    def _first_chunk():
        s_scr[d] = s0_ref[0]
        if snapshot:
            snap_scr[d] = s0_ref[0]

    @pl.when(d == 0)
    def _spread_columns():         # once a chunk, read by every block
        def eight(g, carry):
            base = pl.multiple_of(g * _GROUP, _GROUP)
            back = lax.rem(_CHUNK - base, _CHUNK)   # lane base -> lane 0
            for tile, out in ((bt_ref, bb_scr), (ct_ref, cb_scr)):
                front = pltpu.roll(tile[0], back, 1)
                for j in range(_GROUP):
                    out[base + j] = jnp.broadcast_to(
                        front[:, j:j + 1], (n_state, _LANES))
            return carry

        lax.fori_loop(0, groups, eight, 0)

    def lanes(col):                # (N, 128) -> (N, block), no copy
        return jnp.concatenate([col] * (block // _LANES), axis=1)

    A = a_ref[...]

    def eight_columns(g, carry):
        base = pl.multiple_of(g * _GROUP, _GROUP)
        rows = pl.ds(base, _GROUP)
        dt8 = dt_ref[0, rows, :]
        dtx8 = dt8 * x_ref[0, rows, :]
        s = s_scr[d]
        if snapshot:
            snap, wanted = snap_scr[d], cap_ref[0] - (c * _CHUNK + base)
        for j in range(_GROUP):    # the reference chain's operations
            s = (jnp.exp(dt8[j:j + 1, :] * A) * s
                 + dtx8[j:j + 1, :] * lanes(bb_scr[base + j]))
            y_ref[0, pl.ds(base + j, 1), :] = jnp.sum(
                s * lanes(cb_scr[base + j]), axis=0, keepdims=True)
            if snapshot:
                snap = jnp.where(wanted == j, s, snap)
        s_scr[d] = s
        if snapshot:
            snap_scr[d] = snap
        return carry

    lax.fori_loop(0, groups, eight_columns, 0)
    st_ref[0] = s_scr[d]
    if snapshot:
        snap_ref[0] = snap_scr[d]


def _ceil_to(n: int, m: int) -> int:
    return -(-n // m) * m


def _call(dt, x, A, Bm, Cm, s0, capture, *, interpret):
    from jax.experimental.pallas import tpu as pltpu

    B, T, di = x.shape
    N = A.shape[0]
    Tp, dip = _ceil_to(T, _CHUNK), _ceil_to(di, _LANES)
    # the widest block that divides d_inner
    block = next(b for b in (_BLOCK, 256, _LANES) if dip % b == 0)
    snapshot = capture is not None
    f32 = jnp.float32

    def fit(a, time=0, lanes=dip - di):   # identity columns, idle lanes
        pads = [(0, 0)] * (a.ndim - 2) + [(0, time), (0, lanes)]
        return jnp.pad(a.astype(f32), pads)

    def columns(m):                # (B, T, N) -> (B, N, Tp)
        return fit(m.swapaxes(1, 2), lanes=Tp - T)

    n_blocks = dip // block
    time_block = pl.BlockSpec((1, _CHUNK, block),
                              lambda b, c, d, cap: (b, c, d))
    state_block = pl.BlockSpec((1, N, block),
                               lambda b, c, d, cap: (b, 0, d))
    column_block = pl.BlockSpec((1, N, _CHUNK),
                                lambda b, c, d, cap: (b, 0, c))
    state = jax.ShapeDtypeStruct((B, N, dip), f32)
    scratch = [pltpu.VMEM((n_blocks, N, block), f32),
               pltpu.VMEM((_CHUNK, N, _LANES), f32),
               pltpu.VMEM((_CHUNK, N, _LANES), f32)]
    out_shape = [jax.ShapeDtypeStruct((B, Tp, dip), f32), state]
    out_specs = [time_block, state_block]
    if snapshot:
        out_shape.append(state)
        out_specs.append(state_block)
        scratch.append(pltpu.VMEM((n_blocks, N, block), f32))
    cap = jnp.reshape(jnp.asarray(
        capture if snapshot else -1, jnp.int32), (1,))
    outs = pl.pallas_call(
        functools.partial(_kernel, block=block, snapshot=snapshot),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Tp // _CHUNK, n_blocks),
            in_specs=[time_block, time_block,
                      pl.BlockSpec((N, block),
                                   lambda b, c, d, cap: (0, d)),
                      column_block, column_block, state_block],
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=scopes.SSM_SCAN,
    )(cap, fit(dt, Tp - T), fit(x, Tp - T), fit(A), columns(Bm),
      columns(Cm), fit(s0))
    y, s = outs[0][:, :T, :di], outs[1][..., :di]
    return y, s, (outs[2][..., :di] if snapshot else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _scan(dt, x, A, Bm, Cm, s0, capture, interpret, ref_chunk):
    return _call(dt, x, A, Bm, Cm, s0, capture, interpret=interpret)


def _scan_fwd(dt, x, A, Bm, Cm, s0, capture, interpret, ref_chunk):
    out = _call(dt, x, A, Bm, Cm, s0, capture, interpret=interpret)
    return out, (dt, x, A, Bm, Cm, s0, capture)


def _scan_bwd(interpret, ref_chunk, res, cts):
    *operands, capture = res
    _, vjp = jax.vjp(
        lambda *a: selective_scan_reference(*a, ref_chunk, capture),
        *operands)
    no_grad = None if capture is None else np.zeros(
        np.shape(capture), jax.dtypes.float0)
    return (*vjp(cts), no_grad)


_scan.defvjp(_scan_fwd, _scan_bwd)


@functools.partial(jax.jit, static_argnames=("interpret", "ref_chunk"))
def selective_scan(dt, x, A, Bm, Cm, s0, capture=None, *,
                   interpret: bool = False, ref_chunk: int = 32):
    """`selective_scan_reference`'s contract as one Pallas call.

    `ref_chunk` is the reference chain's chunk in the backward pass.
    ``interpret=True`` runs the kernel in the Pallas interpreter (the
    CPU tests).  Jitted: the Mamba walks of one program (before and
    after an attention layer) share one trace and one lowering of the
    kernel."""
    return _scan(dt, x, A, Bm, Cm, s0, capture, interpret, ref_chunk)


__all__ = ["selective_scan", "selective_scan_reference"]
