"""The held experts' SwiGLU as one kernel that streams each touched
expert's weights: a decode wave's few rows a group, and a prefill's
many.

A decode wave hands a chip's experts two or three rows each, and many
none (models/experts.py): what the layer costs is then the touched
experts' bytes, 6.3 MB each at Laguna's widths and 88 MB at Kimi-K2's,
and the arithmetic hides under them if and only if the next weights are
on their way while these multiply.  A prefill hands them hundreds, and
the arithmetic is as large as the bytes.  `grouped_swiglu` is one
``pallas_call`` of that name for both:

  * **A visit is one row tile of one group.**  The visits' groups (and
    tiles) are counted out of `sizes` in ``jnp`` and prefetched as
    scalars; they drive the weights' index maps, so an expert without
    rows is never fetched, one with rows in several tiles is visited
    once a tile with its weights left where they are (where they go in
    one step), and the grid's steps past the last visit name the last
    visit's blocks again (nothing is fetched for them) and do nothing.
    The weights stay the whole stack ``(L, g, d, f)`` / ``(L, g, f,
    d)``: layer and expert are picked by the index map, never sliced (a
    slice handed to a kernel is copied first, every expert of the
    layer).
  * **Few rows a group: every group on row tiles of its own**
    (``aligned``, tiles of `ROW_TILE`).  Group e's ``sizes[e]`` rows
    start at ``ROW_TILE * sum(ceil(sizes[:e] / ROW_TILE))``: a group of
    two or three rows that straddled a tile would be visited twice and
    fetch nothing less.  The rows between a group's last and the next
    group's first belong to nobody: they are multiplied with their tile
    and whatever they hold comes back in their places (a row's product
    reads no other row, so nothing of them reaches a row that is
    owned); rows past the last group's tile are not written at all.
  * **Many rows a group: the groups end to end** (``aligned=False``,
    tall tiles, `visit_rows`).  Group e's rows start where group e-1's
    end, and no row is added to a pass.  A tile that two or more groups
    share is visited once for each, consecutively, so its rows and its
    result stay in VMEM between them; each visit writes only its own
    group's rows of the result (a row mask from the group's
    ``[start, end)`` against the tile's rows), and the tile's last
    visit writes the tile out.  A visit multiplies `TALL` rows at a
    time and only where its group has any.  Rows past the last group's
    end are nobody's and come back unspecified.  That is the way of
    ``jax.experimental.pallas.ops.tpu.megablox``; what it pays is a
    visit more for every tile's edge a group straddles (and, where the
    expert's width goes in chunks, the expert's bytes again).
  * **Slabs in, slabs out.**  Rows come and go as `moe_dispatch` writes
    and `moe_combine` reads them, SLABS ``(R, d / 128, 128)``
    (ops/moe_dispatch.py `slabs`), and are turned into rows, and the
    result's rows into slabs, in VMEM, so that no ``(R, d)`` matrix is
    re-laid between the three kernels (XLA makes that reshape a copy of
    all R rows, owned or not): a short tile's a row at a time; a tall
    tile's a column block at a time, by sublanes strided over the
    tile's slabs.  The caller reads owned rows only.
  * **Gate, up, SwiGLU and down in one body** (`_swiglu`), over chunks
    `tf` of the expert width: ``h = silu(x Wg[:, tf]) * (x Wu[:, tf])``
    in float32, rounded to the compute dtype, ``o += h Wd[tf, :]`` in
    float32.  `h` never leaves VMEM.
  * **The next weights in flight.**  `chunk` takes the widest `tf` whose
    three blocks stay within `_STEP_BYTES`, so a step moves megabytes
    and two steps' blocks fit the VMEM asked for: an expert whole at
    ``d`` 2,048, ``f`` 512; 128 columns of 2,048 at ``d`` 7,168.

Off the chip the same contract is `lax.ragged_dot` over the groups'
sizes, rounded up to whole row tiles where the groups are aligned
(models/experts.py `fused_reference`), which is what
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
#: rows multiplied at a time where a group has many (a prefill's
#: pass): the matrix unit's height
TALL = 128
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


def visit_rows(rows: int) -> int:
    """Rows of a visit's tile where groups begin where they begin
    (`grouped_swiglu`, ``aligned=False``) and a pass holds `rows`: two
    of `TALL`, one where the pass has fewer than four.  The rows are
    multiplied `TALL` at a time whatever the tile, and only where the
    group has any, so a taller tile costs no arithmetic: it saves
    visits (fewer tiles, fewer groups that straddle one; where the
    width goes in chunks every visit streams the whole expert again).
    On the chip a pass of Laguna's 4,096 bucket took 3.92 ms at 128
    rows a tile and 3.56 at 256, Kimi-K2's 8,192 3.50 and 2.80
    (PERF.md, PR 47)."""
    return 2 * TALL if rows >= 4 * TALL else TALL


def visits(sizes, tm: int = ROW_TILE, aligned: bool = True):
    """Visits a call makes each group of `sizes`: with `aligned` the
    row tiles it fills (`row_tiles`), else the row tiles its rows lie
    in, begun at the running sum of the sizes before it (one more than
    it fills for every tile's edge it straddles)."""
    if aligned:
        return row_tiles(sizes, tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    return jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1, 0)


def _swiglu(x, wg_ref, wu_ref, wd_ref, dtype):
    """One chunk of one group's weights on rows x (rows, d) in `dtype`:
    their part of the down projection's sum, (rows, d) float32."""
    f32 = jnp.float32
    gate = jnp.dot(x, wg_ref[...].astype(dtype),
                   preferred_element_type=f32)
    up = jnp.dot(x, wu_ref[...].astype(dtype),
                 preferred_element_type=f32)
    h = (jax.nn.silu(gate) * up).astype(dtype)
    return jnp.dot(h, wd_ref[...].astype(dtype),
                   preferred_element_type=f32)


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

        y = _swiglu(x_rows[...].astype(dtype), wg_ref, wu_ref, wd_ref,
                    dtype)

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


def _straddling_kernel(gid_ref, tid_ref, lo_ref, hi_ref, meta_ref, x_ref,
                       wg_ref, wu_ref, wd_ref, o_ref, x_rows, y_rows, *,
                       dtype, slab):
    """`_kernel` where a visit is one (row tile, group) pair whose rows
    meet: tid (V,) the tile of each visit, lo, hi (V,) its group's rows
    of that tile; x, o (tm * s, l) the tile's slabs, sublane on
    sublane: column block k of the tile's rows is every s-th sublane
    from the k-th on.  scratch: the tile's rows (tm, d) in `dtype` and
    its result's in float32, kept over the tile's visits; a visit
    writes its own group's rows there and the tile's last visit the
    whole tile out."""
    v, j = pl.program_id(0), pl.program_id(1)
    tm, d = y_rows.shape
    s, l = slab
    sub = min(tm, TALL)
    n = meta_ref[0]

    @pl.when(v < n)
    def _visit():
        tile, lo, hi = tid_ref[v], lo_ref[v], hi_ref[v]
        fresh = (v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != tile)
        last = (v == n - 1) | (
            tid_ref[jnp.minimum(v + 1, pl.num_programs(0) - 1)] != tile)

        @pl.when(fresh & (j == 0))
        def _rows_of_slabs():
            for k in range(s):
                x_rows[:, k * l:(k + 1) * l] = x_ref[
                    pl.ds(k, tm, stride=s), :].astype(dtype)

        for at in range(0, tm, sub):
            @pl.when((lo < at + sub) & (hi > at))
            def _rows(at=at):
                y = _swiglu(x_rows[at:at + sub, :], wg_ref, wu_ref, wd_ref,
                            dtype)
                row = at + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
                mine = (row >= lo) & (row < hi)

                @pl.when(j == 0)
                def _first():
                    y_rows[at:at + sub, :] = jnp.where(
                        mine, y, y_rows[at:at + sub, :])

                @pl.when(j > 0)
                def _further():
                    y_rows[at:at + sub, :] += jnp.where(mine, y, 0.0)

        @pl.when(last & (j == pl.num_programs(1) - 1))
        def _slabs_of_rows():
            for k in range(s):
                o_ref[pl.ds(k, tm, stride=s), :] = y_rows[
                    :, k * l:(k + 1) * l]


@functools.partial(jax.jit, static_argnames=("dtype", "tm", "tf", "aligned",
                                             "interpret"))
def grouped_swiglu(xs, w_gate, w_up, w_down, sizes, layer=None, *,
                   dtype=jnp.bfloat16, tm: int = ROW_TILE, tf=None,
                   aligned: bool = True, interpret: bool = False):
    """xs (R, s, l) float32 slabs of rows in grouped order (R a
    multiple of `tm`), with `aligned` every group begun on a row tile
    of `tm`, else where the last one ended; w_gate, w_up (g, d, f) and
    w_down (g, f, d), or with `layer` (an int32 scalar) stacks
    (L, g, ...) of which layer `layer`'s experts are the groups; sizes
    (g,) int32.
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
    # the static bound of the visits: a tile each, and one more for
    # every group that can begin inside one
    V, J = R // tm + (0 if aligned else g), f // tf
    i32 = jnp.int32
    # visit v's group: the first whose running count of visits passes
    # v; the steps past the last visit repeat it
    sizes = sizes.astype(i32)
    made = visits(sizes, tm, aligned)
    filled = jnp.cumsum(made)
    n = filled[-1]
    v = jnp.minimum(jnp.arange(V, dtype=i32), jnp.maximum(n - 1, 0))
    before = filled[None, :] <= v[:, None]
    gid = jnp.minimum(jnp.sum(before, axis=1, dtype=i32), g - 1)
    meta = jnp.stack([n, jnp.asarray(layer, i32)])

    def part(v, j, meta):
        """The chunk a step names: past the last visit its last."""
        return jnp.where(v < meta[0], j, J - 1)

    def wide(v, j, gid, *more):
        return more[-1][1], gid[v], 0, part(v, j, more[-1])

    def tall(v, j, gid, *more):
        return more[-1][1], gid[v], part(v, j, more[-1]), 0

    if aligned:
        def rows(v, j, gid, meta):
            return jnp.minimum(v, jnp.maximum(meta[0] - 1, 0)), 0, 0

        prefetch, kernel = (gid, meta), functools.partial(_kernel,
                                                          dtype=dtype)
        block, room, x_dtype = (tm,) + slab, 12, jnp.float32
    else:
        # visit v's tile: its group's first and as many on as the
        # group's visits before it; the group's rows of that tile
        # (sums over the groups before it, and up to it, as `gid` is:
        # nothing is gathered)
        upto = (filled - made)[None, :] <= v[:, None]
        first = jnp.sum(jnp.where(before, sizes, 0), axis=1)
        end = jnp.sum(jnp.where(upto, sizes, 0), axis=1)
        tid = jnp.clip(first // tm + v
                       - jnp.sum(jnp.where(before, made, 0), axis=1), 0,
                       R // tm - 1)
        lo = jnp.clip(first - tid * tm, 0, tm)
        hi = jnp.clip(end - tid * tm, 0, tm)

        def rows(v, j, gid, tid, *more):
            return tid[v], 0

        prefetch, kernel = (gid, tid, lo, hi, meta), functools.partial(
            _straddling_kernel, dtype=dtype, slab=slab)
        xs = xs.reshape(R * slab[0], slab[1])
        block, room, x_dtype = (tm * slab[0], slab[1]), 8, dtype
    w_bytes = 3 * d * tf * w_gate.dtype.itemsize
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(V, J),
            in_specs=[pl.BlockSpec(block, rows),
                      pl.BlockSpec((None, None, d, tf), wide),
                      pl.BlockSpec((None, None, d, tf), wide),
                      pl.BlockSpec((None, None, tf, d), tall)],
            out_specs=pl.BlockSpec(block, rows),
            scratch_shapes=[pltpu.VMEM((tm, d), x_dtype),
                            pltpu.VMEM((tm, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(xs.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * w_bytes + room * tm * d * 4 + (8 << 20)),
        interpret=interpret,
        name=scopes.GROUPED_SWIGLU,
    )(*prefetch, xs, w_gate, w_up, w_down).reshape((R,) + slab)


__all__ = ["grouped_swiglu", "chunk", "row_tiles", "visit_rows", "visits",
           "ROW_TILE", "TALL"]
