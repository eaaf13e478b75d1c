"""ops/moe_dispatch.py: the expert layer's dispatch and combine
kernels, in the Pallas interpreter, held to their `jnp` references (a
stable sort, a gather, a scatter-add): ragged groups, an empty group, a
group that spans row tiles, a window of the grouped order that starts
inside a group, rows that hold no token, a stage that fills.

Toy widths: rows of 256 (slabs of (2, 128)) and of 32 (one short
sublane: a d that is no multiple of 128).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import scopes
from ray_tpu.ops import moe_dispatch as md

K, G, E = 4, 6, 16

#: name -> (N, d, rows, lo, how the choices are drawn)
CASES = {
    "ragged_groups": (64, 256, 128, 0, "uniform"),
    "an_empty_group": (64, 256, 128, 0, "expert_2_unchosen"),
    "one_group_takes_every_row": (96, 256, 128, 0, "all_choose_expert_3"),
    "a_group_spans_row_tiles": (96, 256, 32, 32, "all_choose_expert_3"),
    "the_window_starts_inside_a_group": (64, 256, 40, 24, "uniform"),
    "the_window_is_past_every_row": (64, 256, 32, 4096, "uniform"),
    "no_row_is_local": (32, 256, 32, 0, "none_local"),
    "rows_without_a_token": (64, 256, 128, 0, "half_idle"),
    "the_stage_fills": (128, 256, 1024, 0, "all_local"),
    "a_short_sublane": (24, 32, 64, 0, "uniform"),
    "an_odd_row_count": (7, 32, 32, 0, "uniform"),
}


def _problem(name):
    N, d, rows, lo, draw = CASES[name]
    rng = np.random.default_rng(len(name))
    score = rng.random((N, E))
    if draw == "expert_2_unchosen":
        score[:, 2] = -1.0
    elif draw == "all_choose_expert_3":
        score[:, :G] = -1.0                 # and no other held expert
        score[:, 3] = 2.0
    elif draw == "none_local":
        score[:, :G] = -1.0
    elif draw == "all_local":
        score[:, G:] = -1.0
    chosen = np.argsort(-score, axis=1)[:, :K]
    loc = np.where(chosen < G, chosen, G)
    if draw == "half_idle":
        loc[rng.random(N) < 0.5] = G
    counts = np.array([(loc == e).sum() for e in range(G)])
    starts = np.cumsum(counts) - counts
    as_jnp = lambda a, dt: jnp.asarray(a, dt)  # noqa: E731
    return dict(
        rows=rows, lo=lo, n_local=int(counts.sum()), counts=counts,
        loc=as_jnp(loc, jnp.int32), starts=as_jnp(starts, jnp.int32),
        x=as_jnp(rng.standard_normal((N, d)), jnp.float32),
        ys=md.slabs(as_jnp(rng.standard_normal((rows, d)), jnp.float32)),
        base=as_jnp(rng.standard_normal((N, d)), jnp.float32),
        w=as_jnp(rng.random((N, K)), jnp.float32))


@pytest.mark.parametrize("name", CASES)
def test_dispatch_copies_each_local_row_to_its_place(name):
    p = _problem(name)
    got = md.moe_dispatch(p["x"], p["loc"], p["starts"], p["lo"],
                          rows=p["rows"], interpret=True)
    want = md.dispatch_reference(p["x"], p["loc"], p["starts"], p["lo"],
                                 rows=p["rows"])
    assert got.shape == want.shape and got.dtype == want.dtype
    # the rows the window holds; the others are whatever the buffer was
    held = min(max(p["n_local"] - p["lo"], 0), p["rows"])
    np.testing.assert_array_equal(np.asarray(got[:held]),
                                  np.asarray(want[:held]))
    if name == "one_group_takes_every_row":
        assert p["counts"][3] == p["x"].shape[0] == p["n_local"]


@pytest.mark.parametrize("name", CASES)
def test_combine_adds_each_result_row_onto_its_token(name):
    p = _problem(name)
    got = md.moe_combine(p["base"], p["ys"], p["loc"], p["w"], p["starts"],
                         p["lo"], interpret=True)
    want = md.combine_reference(p["base"], p["ys"], p["loc"], p["w"],
                                p["starts"], p["lo"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)
    if p["n_local"] <= p["lo"]:
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(p["base"]))


def test_windows_of_the_grouped_order_add_up():
    """Three passes of 40 rows over 96 local assignments' worth of
    tokens give what one pass of all rows gives: nothing dropped, none
    taken twice."""
    p = _problem("ragged_groups")
    whole = md.combine_reference(
        p["base"], jnp.tile(p["ys"], (2, 1, 1))[:p["n_local"]], p["loc"],
        p["w"], p["starts"], 0)
    ys = jnp.tile(p["ys"], (2, 1, 1))
    y, step = p["base"], 40
    for lo in range(0, p["n_local"], step):
        y = md.moe_combine(y, ys[lo:lo + step], p["loc"], p["w"],
                           p["starts"], lo, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(whole), atol=1e-5)


def test_rows_narrower_than_a_word_are_refused():
    p = _problem("ragged_groups")
    with pytest.raises(ValueError, match="cannot be copied alone"):
        md.moe_dispatch(p["x"].astype(jnp.bfloat16), p["loc"], p["starts"],
                        0, rows=32, interpret=True)


@pytest.mark.parametrize("n,d,want", [
    (8192, 7168, 128), (64, 7168, 64), (1024, 7168, 128), (96, 256, 32),
    (7, 32, 1), (3072, 32, 256)])
def test_a_grid_step_takes_a_power_of_two_of_tokens(n, d, want):
    assert md.token_tile(n, d) == want


def test_the_kernels_are_named():
    assert {scopes.MOE_DISPATCH, scopes.MOE_COMBINE} <= set(scopes.KERNELS)
    assert (scopes.MOE_DISPATCH, scopes.MOE_COMBINE) == ("moe_dispatch",
                                                        "moe_combine")
