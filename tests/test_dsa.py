"""The lightning indexer's scores and its selection (ray_tpu/ops/dsa.py),
on the CPU at small sizes: the two forms of the selection against each
other and against a sort, ties among them; the blockwise and the paged
scores against the plain product; the gather by (block table, offset)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import dsa

J, D = 4, 16


def _problem(seed, T, S):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (T, J, D)),
            jax.random.normal(ks[1], (T, J)) * 0.1,
            jax.random.normal(ks[2], (S, D)))


def _by_sorting(scores, ok, topk):
    """The selection as the equations state it: a stable sort of each
    row's reachable scores, highest first, lower position first among
    equals."""
    scores, ok = np.asarray(scores), np.asarray(ok)
    out = np.zeros(ok.shape, bool)
    for r in range(scores.shape[0]):
        reach = np.flatnonzero(ok[r])
        order = reach[np.argsort(-scores[r, reach], kind="stable")]
        out[r, order[:topk]] = True
    return out


REACH = jnp.asarray([0, 3, 10, 20, 30, 49, -1, 7])


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "tied"])
@pytest.mark.parametrize("topk", [1, 8, 64])
def test_both_forms_select_what_a_sort_selects(ties, topk):
    scores = jax.random.normal(jax.random.PRNGKey(topk), (8, 50))
    if ties:
        # a few distinct values, and exact zeros as a shut ReLU gives
        scores = jnp.maximum(jnp.round(scores * 2) / 2, 0.0)
    ok = jnp.arange(50)[None, :] <= REACH[:, None]
    want = _by_sorting(scores, ok, topk)
    mask = np.asarray(dsa.select_mask(scores, ok, topk))
    np.testing.assert_array_equal(mask, want)
    idx, valid = (np.asarray(a) for a in dsa.select_top(scores, ok, topk))
    assert idx.shape == (8, min(topk, 50))
    top = np.zeros_like(want)
    for r in range(8):
        top[r, idx[r][valid[r]]] = True
    np.testing.assert_array_equal(top, want)
    counts = np.minimum(np.asarray(REACH) + 1, topk)
    np.testing.assert_array_equal(mask.sum(-1), counts)
    np.testing.assert_array_equal(np.asarray(dsa.selected_count(ok, topk)),
                                  counts)


def test_the_order_of_floats_is_kept():
    x = jnp.asarray([-jnp.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, jnp.inf])
    keys = np.asarray(dsa._sortable(x))
    assert keys.dtype == np.uint32 and (np.diff(keys.astype(np.int64)) > 0
                                        ).all()


@pytest.mark.parametrize("top", [0, 15, 16, 40, 63])
def test_blockwise_scores_are_the_plain_product(top):
    qi, w, k = _problem(1, 8, 64)
    want = np.asarray(dsa.index_scores(qi, w, k))
    got = np.asarray(dsa.index_scores_block(qi, w, k, jnp.int32(top), 16))
    walked = (top + 16) // 16 * 16
    np.testing.assert_allclose(got[:, :walked], want[:, :walked], atol=1e-6)
    assert not got[:, walked:].any()


def test_scores_are_the_equation():
    qi, w, k = _problem(2, 3, 5)
    want = sum(np.asarray(w)[:, j, None] * np.maximum(
        np.asarray(qi)[:, j] @ np.asarray(k).T, 0.0) for j in range(J))
    np.testing.assert_allclose(np.asarray(dsa.index_scores(qi, w, k)), want,
                               atol=1e-5)


def test_the_step_reads_the_paged_pool_as_the_block_walk_reads_rows():
    """Two rows' contexts scattered over a pool's blocks, the new
    token's own key beside them: one decode column a row scores what a
    block of queries scores over the same keys laid end to end."""
    bs, nb, L = 4, 6, 2
    qi, w, _ = _problem(3, 2, 1)
    keys = jax.random.normal(jax.random.PRNGKey(9), (2, nb * bs, D))
    tables = jnp.asarray([[3, 7, 1, 9, 5, 11], [2, 4, 6, 8, 10, 12]])
    pool = jnp.zeros((L, 13, bs, D)).at[1, tables].set(
        keys.reshape(2, nb, bs, D))
    pos = jnp.asarray([9, 22])
    fresh = jax.random.normal(jax.random.PRNGKey(10), (2, D))
    got = dsa.index_scores_step(qi, w, pool, 1, tables, pos, fresh)
    for r in range(2):
        row = keys[r].at[pos[r]].set(fresh[r])
        want = dsa.index_scores_block(qi[r:r + 1], w[r:r + 1], row,
                                      jnp.int32(nb * bs - 1), bs)
        np.testing.assert_allclose(np.asarray(got[r]), np.asarray(want[0]),
                                   atol=1e-6)


@pytest.mark.parametrize("topk", [4, 20, 100])
def test_a_prefills_mask_is_each_querys_selection(topk):
    """Queries behind a prefix of 10, three pad columns first."""
    T, S, pad, prefix = 32, 64, 3, 10
    qi, w, k = _problem(4, T, S)
    col = jnp.arange(T)
    reach = jnp.where(col >= pad, prefix + col - pad, -1)
    got = np.asarray(dsa.select_prefill(qi, w, k, reach, topk, 8, 16))
    ok = jnp.arange(S)[None, :] <= reach[:, None]
    want = _by_sorting(dsa.index_scores(qi, w, k), ok, topk)
    np.testing.assert_array_equal(got, want)
    assert not got[:pad].any()


@pytest.mark.parametrize("width", [128, 8], ids=["whole_tiles", "narrow"])
def test_selected_rows_are_gathered_by_table_and_offset(width):
    bs, L = 4, 3
    pool = jax.random.normal(jax.random.PRNGKey(5), (L, 9, bs, width))
    tables = jnp.asarray([[3, 1, 7], [2, 8, 4]])
    idx = jnp.asarray([[0, 5, 11, 6], [9, 9, 2, 1]])
    rows = dsa.pool_rows(pool)
    assert rows.shape == (L, 9 * bs, width)
    got = np.asarray(dsa.gather_selected(rows, jnp.int32(2), tables, idx,
                                         bs))
    for r in range(2):
        for j, s in enumerate(np.asarray(idx[r])):
            np.testing.assert_array_equal(
                got[r, j], np.asarray(pool[2, tables[r, s // bs], s % bs]))
