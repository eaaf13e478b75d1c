"""The fused grouped SwiGLU (ray_tpu/ops/grouped_swiglu.py) in the
Pallas interpreter at toy widths, against `experts._grouped`: rows that
are few a group over the groups' sizes rounded up to whole row tiles,
rows that are many over the sizes as they are (groups that begin where
they begin) -- what `routed_experts` runs off the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import scopes
from ray_tpu.models import experts as ex
from ray_tpu.ops.grouped_swiglu import (ROW_TILE, TALL, chunk,
                                        grouped_swiglu, row_tiles,
                                        visit_rows, visits)
from ray_tpu.ops.moe_dispatch import rows_of, slabs

T = ROW_TILE


def _weights(shape, d, f, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w_gate": jax.random.normal(ks[0], shape + (d, f)) * 0.3,
            "w_up": jax.random.normal(ks[1], shape + (d, f)) * 0.3,
            "w_down": jax.random.normal(ks[2], shape + (f, d)) * 0.3}


def _rows(counts, d, spare_tiles, fill, seed=3):
    """Rows in the kernel's order: every group begun on a row tile;
    rows nobody owns hold `fill`.  -> (xs (R, d), owned (R,) bool)."""
    counts = np.asarray(counts)
    tiles = -(-counts // T)
    first = (np.cumsum(tiles) - tiles) * T
    R = (int(tiles.sum()) + spare_tiles) * T
    owned = np.zeros(R, bool)
    for at, n in zip(first, counts):
        owned[at:at + n] = True
    xs = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (R, d)))
    return np.where(owned[:, None], xs, fill).astype(np.float32), owned


#: name -> (layers or None, layer, d, f, tf or None, the groups' sizes)
CASES = {
    "empty_groups_between_touched_ones": (None, None, 32, 16, None,
                                          [2, 0, 0, 3, 0, 1, 0, 0]),
    "the_first_and_last_groups_empty": (None, None, 32, 16, None,
                                        [0, 0, 5, 1, 0]),
    "one_group_taller_than_several_tiles": (None, None, 32, 16, None,
                                            [1, 0, 3 * T + 5, 2]),
    "a_group_fills_its_tiles_to_the_row": (None, None, 32, 16, None,
                                           [T, 0, 2 * T, 1]),
    "counts_no_multiple_of_the_sublane_tile": (None, None, 32, 16, None,
                                               [7, 9, 1, 17, 15]),
    "one_group_alone": (None, None, 32, 16, None, [3]),
    "a_stack_with_the_layer_in_the_middle": (3, 1, 32, 16, None,
                                             [2, 0, 19, 0, 1, 4]),
    "a_stack_and_its_last_layer": (3, 2, 32, 16, None, [0, 2, 2, 0]),
    "slabs_of_several_sublanes": (None, None, 384, 16, None, [3, 0, 9, 1]),
    "the_width_in_chunks": (None, None, 128, 256, 128, [0, 17, 0, 5]),
    "the_width_in_chunks_of_a_stack": (2, 1, 128, 384, 128,
                                       [3, 0, T + 1, 0, 2]),
}


@pytest.mark.parametrize("fill", [0.0, np.nan], ids=["zeros", "nans"])
@pytest.mark.parametrize("name", CASES)
def test_owned_rows_are_the_grouped_matmuls(name, fill):
    """Every owned row is what `_grouped` gives it, whatever lies in
    the rows nobody owns: NaNs planted there reach no owned row."""
    L, layer, d, f, tf, counts = CASES[name]
    g = len(counts)
    p = _weights((g,) if L is None else (L, g), d, f)
    xs, owned = _rows(counts, d, spare_tiles=2, fill=fill)
    sizes = jnp.asarray(counts, jnp.int32)
    lay = None if L is None else jnp.int32(layer)
    got = grouped_swiglu(
        slabs(jnp.asarray(xs)), p["w_gate"], p["w_up"], p["w_down"], sizes,
        lay, dtype=jnp.float32, tf=tf, interpret=True)
    assert got.shape == slabs(xs).shape and got.dtype == np.float32
    got = np.asarray(rows_of(got))
    want = np.asarray(rows_of(ex.fused_reference(
        slabs(jnp.asarray(np.where(owned[:, None], xs, 0.0))), p, sizes,
        jnp.float32, lay)))
    assert np.isfinite(got[owned]).all()
    np.testing.assert_allclose(got[owned], want[owned], atol=1e-4,
                               rtol=1e-4)
    # and what one expert alone gives its rows
    tiles = -(-np.asarray(counts) // T)
    first = (np.cumsum(tiles) - tiles) * T
    e = int(np.argmax(counts))
    one = {k: (v if L is None else v[layer])[e] for k, v in p.items()}
    mine = slice(first[e], first[e] + counts[e])
    np.testing.assert_allclose(
        got[mine], np.asarray(ex._swiglu(jnp.asarray(xs[mine]), one,
                                         jnp.float32)),
        atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("stacked", [False, True])
def test_every_group_empty(stacked):
    """No visit: nothing is multiplied and nothing raises; no row is
    owned, so there is nothing to read."""
    p = _weights((2, 4) if stacked else (4,), 32, 16)
    xs = slabs(jnp.full((3 * T, 32), jnp.nan, jnp.float32))
    out = grouped_swiglu(xs, p["w_gate"], p["w_up"], p["w_down"],
                         jnp.zeros((4,), jnp.int32),
                         jnp.int32(1) if stacked else None,
                         dtype=jnp.float32, interpret=True)
    assert out.shape == xs.shape


def test_the_compute_dtype_rounds_where_the_grouped_matmuls_round():
    """bf16 operands, float32 sums, `h` rounded once before the down
    projection: to the last bits of `_grouped` in bf16."""
    counts = [3, 0, 18, 1]
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                     _weights((4,), 128, 128))
    xs, owned = _rows(counts, 128, spare_tiles=1, fill=0.0)
    sizes = jnp.asarray(counts, jnp.int32)
    got = np.asarray(rows_of(grouped_swiglu(
        slabs(jnp.asarray(xs)), p["w_gate"], p["w_up"], p["w_down"], sizes,
        dtype=jnp.bfloat16, interpret=True)))
    want = np.asarray(rows_of(ex.fused_reference(
        slabs(jnp.asarray(xs)), p, sizes, jnp.bfloat16)))
    np.testing.assert_allclose(got[owned], want[owned], atol=2e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("bad", [{"rows": T + 1}, {"tf": 48}])
def test_a_rest_of_rows_or_of_width_is_refused(bad):
    p = _weights((2,), 32, 128)
    xs = slabs(jnp.zeros((bad.get("rows", T), 32), jnp.float32))
    with pytest.raises(ValueError, match="rest"):
        grouped_swiglu(xs, p["w_gate"], p["w_up"], p["w_down"],
                       jnp.asarray([1, 0], jnp.int32), dtype=jnp.float32,
                       tf=bad.get("tf"), interpret=True)


@pytest.mark.parametrize("d,f,want", [(2048, 512, 512), (7168, 2048, 128),
                                      (32, 16, 16), (4096, 1536, 256)])
def test_the_chunk_follows_the_widths(d, f, want):
    """Laguna's expert goes whole, 3 x 2 MB a step; Kimi-K2's in chunks
    of 128 columns, 5.5 MB a step; a toy's whole whatever its width."""
    tf = chunk(d, f)
    assert tf == want and f % tf == 0
    assert tf == f or 3 * d * tf * 2 <= 8 << 20


def test_a_group_is_visited_once_a_row_tile():
    sizes = jnp.asarray([0, 1, T, T + 1, 0, 3 * T], jnp.int32)
    assert row_tiles(sizes).tolist() == [0, 1, 1, 2, 0, 3]
    assert scopes.GROUPED_SWIGLU in scopes.KERNELS


# ---------------------------------------------------------------------------
# groups that begin where they begin (``aligned=False``): a prefill's
# ---------------------------------------------------------------------------

#: name -> (layers or None, layer, d, f, tf or None, rows a tile, the
#: groups' sizes, tiles past the last group's)
END_TO_END = {
    "groups_of_no_row_and_of_one": (None, None, 32, 16, None, T,
                                    [0, 1, 0, 0, 1, 1, 0], 0),
    "a_group_ends_on_a_tiles_last_row": (None, None, 32, 16, None, T,
                                         [3, T - 3, 5, 0, 2], 0),
    "three_groups_in_one_tile": (None, None, 32, 16, None, T,
                                 [T + 1, 2, 2, 1, T], 0),
    "one_group_over_three_tiles": (None, None, 32, 16, None, T,
                                   [3, 2 * T + 2, 1], 0),
    "the_last_tile_part_empty": (None, None, 32, 16, None, 2 * T,
                                 [5, 9, 0, 4], 0),
    "whole_tiles_past_the_last_group": (None, None, 32, 16, None, T,
                                        [2, 0, T + 3], 2),
    "every_group_a_whole_tile": (None, None, 32, 16, None, T,
                                 [T, T, 0, 2 * T], 0),
    "a_stack_and_its_middle_layer": (3, 1, 32, 16, None, T,
                                     [0, 7, 12, 0, 1, 6], 1),
    "the_width_in_chunks": (None, None, 128, 256, 128, 16,
                            [1, 2, 1, 30, 0, 2], 1),
    "the_width_in_chunks_of_a_stack": (2, 1, 128, 384, 128, T,
                                       [3, 0, T + 1, 0, 2], 0),
    "slabs_of_several_sublanes": (None, None, 384, 16, None, T,
                                  [3, 0, 9, 1], 0),
    "a_tile_taller_than_what_is_multiplied_at_a_time": (
        2, 0, 128, 128, None, 2 * TALL, [100, 30, 0, 200, 50], 0),
}


def _end_to_end(name, fill=np.nan, seed=3, dtype=jnp.float32):
    """A case's operands: rows past the last group's hold `fill`."""
    L, layer, d, f, tf, tm, counts, spare = END_TO_END[name]
    g, n = len(counts), int(np.sum(counts))
    p = _weights((g,) if L is None else (L, g), d, f)
    R = (max(-(-n // tm), 1) + spare) * tm
    xs = np.array(jax.random.normal(jax.random.PRNGKey(seed), (R, d)),
                  np.float32)
    xs[n:] = fill
    lay = None if L is None else jnp.int32(layer)
    return p, xs, jnp.asarray(counts, jnp.int32), lay, n, dict(
        dtype=dtype, tm=tm, tf=tf, aligned=False, interpret=True)


@pytest.mark.parametrize("name", END_TO_END)
def test_groups_end_to_end_are_the_grouped_matmuls(name):
    """Every owned row is what `_grouped` gives it on the true sizes
    (no rounding, no row between two groups); NaNs planted in the input
    rows past the last group's reach no owned row."""
    p, xs, sizes, lay, n, how = _end_to_end(name)
    got = grouped_swiglu(slabs(jnp.asarray(xs)), p["w_gate"], p["w_up"],
                         p["w_down"], sizes, lay, **how)
    assert got.shape == slabs(xs).shape and got.dtype == np.float32
    got = np.asarray(rows_of(got))[:n]
    want = np.asarray(ex._grouped(jnp.asarray(np.nan_to_num(xs)), p, sizes,
                                  jnp.float32, lay))[:n]
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # and it is `fused_reference`'s contract, what runs off the chip
    ref = np.asarray(rows_of(ex.fused_reference(
        slabs(jnp.asarray(np.nan_to_num(xs))), p, sizes, jnp.float32, lay,
        how["tm"], False)))[:n]
    np.testing.assert_array_equal(ref, want)


@pytest.mark.parametrize("name", ["three_groups_in_one_tile",
                                  "a_group_ends_on_a_tiles_last_row",
                                  "one_group_over_three_tiles",
                                  "the_width_in_chunks"])
def test_another_groups_rows_leave_this_groups_bits(name):
    """A second call with every other group's rows changed gives each
    group the bits it had: a visit writes its own group's rows of a
    shared tile and no other's."""
    p, xs, sizes, lay, n, how = _end_to_end(name, fill=0.0)
    call = lambda rows: np.asarray(rows_of(grouped_swiglu(  # noqa: E731
        slabs(jnp.asarray(rows)), p["w_gate"], p["w_up"], p["w_down"],
        sizes, lay, **how)))
    first = call(xs)
    ends = np.cumsum(np.asarray(sizes))
    for e in np.nonzero(np.asarray(sizes))[0]:
        mine = slice(ends[e] - int(sizes[e]), ends[e])
        other = -3.0 * xs[::-1].copy()
        other[mine] = xs[mine]
        np.testing.assert_array_equal(call(other)[mine], first[mine])


def test_end_to_end_rounds_where_the_grouped_matmuls_round():
    """bf16 operands, float32 sums, `h` rounded once before the down
    projection, under tall tiles too: to the last bits of `_grouped`
    in bf16 (the interpreter sums in another order than XLA's CPU
    kernel: the chip's bits are chip_smoke.py's to hold)."""
    p, xs, sizes, lay, n, how = _end_to_end(
        "a_tile_taller_than_what_is_multiplied_at_a_time", fill=0.0,
        dtype=jnp.bfloat16)
    p = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    got = np.asarray(rows_of(grouped_swiglu(
        slabs(jnp.asarray(xs)), p["w_gate"], p["w_up"], p["w_down"], sizes,
        lay, **how)))[:n]
    want = np.asarray(ex._grouped(jnp.asarray(xs).astype(jnp.bfloat16), p,
                                  sizes, jnp.bfloat16, lay))[:n]
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("stacked", [False, True])
def test_every_group_empty_end_to_end(stacked):
    p = _weights((2, 4) if stacked else (4,), 32, 16)
    xs = slabs(jnp.full((3 * T, 32), jnp.nan, jnp.float32))
    out = grouped_swiglu(xs, p["w_gate"], p["w_up"], p["w_down"],
                         jnp.zeros((4,), jnp.int32),
                         jnp.int32(1) if stacked else None,
                         dtype=jnp.float32, aligned=False, interpret=True)
    assert out.shape == xs.shape


@pytest.mark.parametrize("sizes,tm,want", [
    ([0, 1, T, T + 1, 0, 3 * T], T, [0, 1, 2, 2, 0, 4]),
    ([T, T, 0, 2 * T], T, [1, 1, 0, 2]),
    ([3, 2, 2, 1], T, [1, 1, 1, 1]),
    ([130, 130, 130], 128, [2, 2, 2]),
    ([32, 32, 32, 40, 32], 128, [1, 1, 1, 2, 1]),
], ids=["straddling", "whole_tiles", "one_tile", "lagunas_4k", "lagunas_1k"])
def test_a_group_is_visited_once_a_tile_it_lies_in(sizes, tm, want):
    """End to end a group is visited once for every tile its rows lie
    in: one more than it fills for every tile's edge it straddles, and
    never more than the static bound (a tile each and one a group)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    made = visits(sizes, tm, aligned=False)
    assert made.tolist() == want
    tiles = -(-int(sizes.sum()) // tm)
    assert int(made.sum()) <= tiles + int((sizes > 0).sum())
    assert visits(sizes, tm).tolist() == row_tiles(sizes, tm).tolist()


@pytest.mark.parametrize("rows,want", [(8192, 256), (33024, 256), (2228, 256),
                                       (512, 256), (511, 128), (320, 128),
                                       (1, 128)])
def test_the_tall_tile_follows_the_passes_rows(rows, want):
    """Two of the matrix unit's heights a tile; one where the pass has
    fewer than four (Kimi-K2's 1,024 bucket: 320 rows in 384, not
    512)."""
    assert visit_rows(rows) == want and want % TALL == 0
