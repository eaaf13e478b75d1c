"""The held experts' SwiGLU on rows that few of each expert's are: one
kernel that streams every touched expert's weights once.

A decode wave hands a chip's experts two or three rows each, and many
none (models/experts.py): what the layer costs is then the touched
experts' bytes, 6.3 MB each at Laguna's widths and 88 MB at Kimi-K2's,
and the arithmetic hides under them if and only if the next weights are
on their way while these multiply.  `grouped_swiglu` is one
``pallas_call`` of that name:

  * **Rows in tiles of their own.**  The rows lie in grouped order with
    every group begun on a whole row tile of `ROW_TILE` rows: group
    e's ``sizes[e]`` rows start at ``ROW_TILE * sum(ceil(sizes[:e] /
    ROW_TILE))``.  The rows between a group's last and the next group's
    first belong to nobody: they are multiplied with their tile and
    whatever they hold comes back in their places (a row's product reads
    no other row, so nothing of them reaches a row that is owned); rows
    past the last group's tile are not written at all.  The caller reads
    owned rows only.  Rows come and go as `moe_dispatch` writes and
    `moe_combine` reads them, SLABS ``(R, d / 128, 128)``
    (ops/moe_dispatch.py `slabs`): a visit's slabs are turned into
    rows, and its result's rows into slabs, in VMEM, a row at a time,
    so that no ``(R, d)`` matrix is re-laid between the three kernels
    (XLA makes that reshape a copy of all R rows, owned or not).
  * **Touched groups only, each once.**  A visit is one row tile of one
    group.  The visits' groups are counted out of `sizes` in ``jnp``
    and prefetched as scalars; they drive the weights' index maps, so an
    expert without rows is never fetched, one taller than a row tile is
    visited once a tile with its weights left where they are, and the
    grid's steps past the last visit name the last visit's blocks again
    (nothing is fetched for them) and do nothing.  The weights stay the
    whole stack ``(L, g, d, f)`` / ``(L, g, f, d)``: layer and expert
    are picked by the index map, never sliced (a slice handed to a
    kernel is copied first, every expert of the layer).
  * **Gate, up, SwiGLU and down in one body**, over chunks `tf` of the
    expert width: ``h = silu(x Wg[:, tf]) * (x Wu[:, tf])`` in float32,
    rounded to the compute dtype, ``o += h Wd[tf, :]`` in float32.  `h`
    never leaves VMEM.
  * **The next weights in flight.**  `chunk` takes the widest `tf` whose
    three blocks stay within `_STEP_BYTES`, so a step moves megabytes
    and two steps' blocks fit the VMEM asked for: an expert whole at
    ``d`` 2,048, ``f`` 512; 128 columns of 2,048 at ``d`` 7,168.

Off the chip the same contract is `lax.ragged_dot` over the groups'
sizes rounded up to whole row tiles (models/experts.py), which is what
tests/test_grouped_swiglu.py holds the kernel to in the interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ray_tpu._private import scopes

#: rows of one visit: a float32 sublane tile (the rows arrive in float32
#: and are rounded in the kernel).  On the chip 8 rows a visit took a
#: Laguna wave 1.559 ms a layer, 16 took 1.579 and 32 1.647 (PERF.md,
#: PR 46), and a group of 2-3 rows leaves fewer rows to nobody
ROW_TILE = 8
#: most bytes of weights one grid step fetches (Kimi-K2's expert in
#: chunks of 128 columns, 5.5 MB a step, took 1.224 ms a layer; of 256,
#: 11 MB a step, 1.255)
_STEP_BYTES = 8 << 20


def chunk(d: int, f: int, itemsize: int = 2) -> int:
    """Columns of the expert width one grid step takes: all `f` where
    the three (d, f) blocks stay within `_STEP_BYTES`, else the largest
    multiple of 128 that divides `f` and does (128 at the least)."""
    if 3 * d * f * itemsize <= _STEP_BYTES or f % 128:
        return f
    tf = max(128, _STEP_BYTES // (3 * d * itemsize) // 128 * 128)
    while f % tf:
        tf -= 128
    return tf


def row_tiles(sizes, tm: int = ROW_TILE):
    """Row tiles each group of `sizes` fills: the visits a call makes
    it, and, times `tm`, the rows grouped order leaves it."""
    return -(-sizes // tm)


def _kernel(gid_ref, meta_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            x_rows, y_rows, *, dtype):
    """gid (V,) the group of each visit, meta = (visits, layer):
    prefetched; x (tm, s, l) the visit's rows as slabs; wg, wu (d, tf)
    and wd (tf, d) the group's weights' chunk; o (tm, s, l) float32;
    scratch: the visit's rows and its result's (tm, d) float32, kept
    over the visit's chunks."""
    j = pl.program_id(1)
    tm, d = x_rows.shape

    @pl.when(pl.program_id(0) < meta_ref[0])
    def _visit():
        @pl.when(j == 0)
        def _rows_of_slabs():
            for i in range(tm):
                x_rows[pl.ds(i, 1), :] = x_ref[i].reshape(1, d)

        f32 = jnp.float32
        x = x_rows[...].astype(dtype)
        gate = jnp.dot(x, wg_ref[...].astype(dtype),
                       preferred_element_type=f32)
        up = jnp.dot(x, wu_ref[...].astype(dtype),
                     preferred_element_type=f32)
        h = (jax.nn.silu(gate) * up).astype(dtype)
        y = jnp.dot(h, wd_ref[...].astype(dtype),
                    preferred_element_type=f32)

        @pl.when(j == 0)
        def _first():
            y_rows[...] = y

        @pl.when(j > 0)
        def _further():
            y_rows[...] += y

        @pl.when(j == pl.num_programs(1) - 1)
        def _slabs_of_rows():
            for i in range(tm):
                o_ref[i] = y_rows[pl.ds(i, 1), :].reshape(o_ref.shape[1:])


@functools.partial(jax.jit,
                   static_argnames=("dtype", "tm", "tf", "interpret"))
def grouped_swiglu(xs, w_gate, w_up, w_down, sizes, layer=None, *,
                   dtype=jnp.bfloat16, tm: int = ROW_TILE, tf=None,
                   interpret: bool = False):
    """xs (R, s, l) float32 slabs of rows in grouped order, every group
    begun on a row tile of `tm` (R a multiple of it); w_gate, w_up
    (g, d, f) and w_down (g, f, d), or with `layer` (an int32 scalar)
    stacks (L, g, ...) of which layer `layer`'s experts are the groups;
    sizes (g,) int32.
    Returns (R, s, l) float32 slabs: each owned row's
    ``(silu(x Wg) * (x Wu)).astype(dtype) Wd``, operands in `dtype`,
    sums in float32; rows nobody owns unspecified (the module's
    docstring)."""
    from jax.experimental.pallas import tpu as pltpu

    R, d = xs.shape[0], xs.shape[1] * xs.shape[2]
    slab = xs.shape[1:]
    if layer is None:
        w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
        layer = 0
    g, f = w_gate.shape[1], w_gate.shape[3]
    tf = chunk(d, f, w_gate.dtype.itemsize) if tf is None else tf
    if R % tm or f % tf:
        raise ValueError(f"{R} rows in tiles of {tm}, width {f} in "
                         f"chunks of {tf}: neither may leave a rest")
    V, J = R // tm, f // tf
    i32 = jnp.int32
    # visit v's group: the first whose running count of tiles passes v;
    # the steps past the last visit repeat it
    filled = jnp.cumsum(row_tiles(sizes.astype(i32), tm))
    n = filled[-1]
    v = jnp.minimum(jnp.arange(V, dtype=i32), jnp.maximum(n - 1, 0))
    gid = jnp.minimum(jnp.sum(filled[None, :] <= v[:, None], axis=1,
                              dtype=i32), g - 1)
    meta = jnp.stack([n, jnp.asarray(layer, i32)])

    def rows(v, j, gid, meta):
        return jnp.minimum(v, jnp.maximum(meta[0] - 1, 0)), 0, 0

    def part(v, j, meta):
        """The chunk a step names: past the last visit its last."""
        return jnp.where(v < meta[0], j, J - 1)

    def wide(v, j, gid, meta):
        return meta[1], gid[v], 0, part(v, j, meta)

    def tall(v, j, gid, meta):
        return meta[1], gid[v], part(v, j, meta), 0

    w_bytes = 3 * d * tf * w_gate.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_kernel, dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(V, J),
            in_specs=[pl.BlockSpec((tm,) + slab, rows),
                      pl.BlockSpec((None, None, d, tf), wide),
                      pl.BlockSpec((None, None, d, tf), wide),
                      pl.BlockSpec((None, None, tf, d), tall)],
            out_specs=pl.BlockSpec((tm,) + slab, rows),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32),
                            pltpu.VMEM((tm, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(xs.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * w_bytes + 12 * tm * d * 4 + (8 << 20)),
        interpret=interpret,
        name=scopes.GROUPED_SWIGLU,
    )(gid, meta, xs, w_gate, w_up, w_down)


__all__ = ["grouped_swiglu", "chunk", "row_tiles", "ROW_TILE"]
