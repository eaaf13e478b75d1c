"""The dropless expert layer that is told which experts it holds
(ray_tpu/models/experts.py), on the CPU at small sizes: the router's
arithmetic, nothing dropped under the worst imbalance, the shares of a
partition of the experts adding up to the uncut layer, the counters."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import experts as ex

D, F, E, K = 32, 16, 16, 4


def _cfg(**kw):
    kw = {"d_model": D, "d_expert": F, "n_routed": E, "top_k": K,
          "route_scale": 2.827, "dtype": jnp.float32, **kw}
    return ex.ExpertsConfig(**kw)


def _x(n, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (n, D), jnp.float32)


def _dense_reference(p, x, cfg, held=None):
    """Every expert applied to every token, masked: no sort, no groups."""
    chosen, w = ex.route(p["router"], x, cfg)
    held = cfg.held_ids if held is None else held
    y = jnp.zeros_like(x)
    for place, e in enumerate(cfg.held_ids):
        if e not in held:
            continue
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        one = {k: v[place] for k, v in p["experts"].items()}
        y = y + mine[:, None] * ex._swiglu(x, one, jnp.float32)
    return y


@pytest.fixture(scope="module")
def whole():
    cfg = _cfg()
    return cfg, ex.experts_init(jax.random.PRNGKey(0), cfg, std=0.3)


def test_the_bias_selects_and_does_not_weigh(whole):
    cfg, p = whole
    x = _x(24)
    router = dict(p["router"], bias=jnp.zeros((E,)).at[5].set(10.0))
    chosen, w = ex.route(router, x, cfg)
    assert bool(jnp.all(jnp.any(chosen == 5, axis=-1)))
    sigma = jax.nn.sigmoid(x @ p["router"]["w"])
    picked = jnp.take_along_axis(sigma, chosen, axis=-1)
    want = picked / picked.sum(-1, keepdims=True) * 2.827
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), rtol=1e-5)


@pytest.mark.parametrize("norm,scale", [(True, 2.827), (True, 1.0),
                                        (False, 1.0)])
def test_weights_are_normalised_and_scaled(whole, norm, scale):
    cfg, p = whole
    cfg = _cfg(norm_topk=norm, route_scale=scale)
    _, w = ex.route(p["router"], _x(10), cfg)
    total = np.asarray(w.sum(-1))
    if norm:
        np.testing.assert_allclose(total, scale, rtol=1e-5)
    else:
        assert np.all(total < K) and np.all(total > 0)


def test_softmax_scoring_takes_the_top_of_a_distribution(whole):
    cfg, p = whole
    cfg = _cfg(scoring="softmax", norm_topk=False, route_scale=1.0)
    chosen, w = ex.route(p["router"], _x(10), cfg)
    probs = jax.nn.softmax(_x(10) @ p["router"]["w"], axis=-1)
    top = jnp.sort(probs, axis=-1)[:, -K:].sum(-1)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), np.asarray(top),
                               rtol=1e-5)


@pytest.mark.parametrize("tiled", [True, False])
@pytest.mark.parametrize("n", [7, 64, 300])
def test_routed_part_equals_every_expert_under_a_mask(whole, n, tiled):
    cfg, p = whole
    x = _x(n, seed=n)
    chosen, w = ex.route(p["router"], x, cfg)
    y, _ = ex.routed_experts(p["experts"], x, chosen, w, cfg, tiled=tiled)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_dense_reference(p, x, cfg)),
                               atol=2e-5)


def test_no_drop_when_every_token_chooses_one_expert(whole):
    """The worst imbalance: the selection bias sends all 200 tokens to
    expert 3 (and to three more); every one of them is computed, through
    several tiles of the sorted rows."""
    cfg, p = whole
    cfg = _cfg(tile_rows=128)
    x = _x(200, seed=9)
    router = dict(p["router"], bias=jnp.zeros((E,)).at[3].set(10.0))
    chosen, w = ex.route(router, x, cfg)
    y, stats = ex.routed_experts(p["experts"], x, chosen, w, cfg)
    want = jnp.zeros_like(x)
    for e in range(E):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)
        one = {k: v[e] for k, v in p["experts"].items()}
        want = want + mine[:, None] * ex._swiglu(x, one, jnp.float32)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    assert float(stats[0]) == 200 * K            # every assignment local
    assert float(stats[2]) >= 200 / (200 * K / E) - 1e-6


@pytest.mark.parametrize("shares", [4, 2, 16])
def test_the_shares_add_up_to_the_uncut_layer(whole, shares):
    """16 experts over `shares` chips (16: EP16 with one expert a chip,
    the deployment models/glm_dsa.py's cell is a sixteenth of): the
    routed parts of all shares plus the shared expert ONCE equal the
    uncut layer."""
    cfg, p = whole
    x = _x(96, seed=5)
    uncut, _ = ex.moe_layer(p, x, cfg)
    per = E // shares
    total = ex.shared_expert(p["shared"], x, cfg)
    touched = 0.0
    for c in range(shares):
        held = ex.held_range(c * per, per)
        part_cfg = _cfg(held=held)
        part = dict(p, experts={k: v[c * per:(c + 1) * per]
                                for k, v in p["experts"].items()})
        chosen, w = ex.route(p["router"], x, part_cfg)
        y, stats = ex.routed_experts(part["experts"], x, chosen, w,
                                     part_cfg)
        total = total + y
        touched += float(stats[0])
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=3e-5)
    assert touched == 96 * K          # every assignment fell on one share


def test_a_stack_of_layers_is_taken_whole(whole):
    """`layer` picks one layer's experts out of a stack without slicing
    it: the other layers' groups have no rows."""
    cfg, p = whole
    other = ex.experts_init(jax.random.PRNGKey(7), cfg, std=0.3)
    stack = jax.tree.map(lambda a, b: jnp.stack([a, b]), other["experts"],
                         p["experts"])
    x = _x(40)
    chosen, w = ex.route(p["router"], x, cfg)
    alone, _ = ex.routed_experts(p["experts"], x, chosen, w, cfg)
    picked, _ = ex.routed_experts(stack, x, chosen, w, cfg,
                                  layer=jnp.int32(1))
    np.testing.assert_allclose(np.asarray(picked), np.asarray(alone),
                               atol=1e-6)


def test_rows_without_a_token_are_routed_nowhere(whole):
    cfg, p = whole
    x = _x(32)
    chosen, w = ex.route(p["router"], x, cfg)
    valid = jnp.arange(32) < 20
    y, stats = ex.routed_experts(p["experts"], x, chosen, w, cfg, valid)
    assert float(jnp.abs(y[20:]).max()) == 0.0
    assert float(stats[0]) == 20 * K
    full, _ = ex.routed_experts(p["experts"], x, chosen, w, cfg)
    np.testing.assert_allclose(np.asarray(y[:20]), np.asarray(full[:20]),
                               atol=1e-6)


def test_the_counters_count_the_held_experts_load():
    cfg = _cfg(held=(0, 1, 2, 3))
    p = ex.experts_init(jax.random.PRNGKey(0), cfg)
    x = _x(50)
    chosen, w = ex.route(p["router"], x, cfg)
    _, stats = ex.routed_experts(p["experts"], x, chosen, w, cfg)
    counts = np.asarray([(np.asarray(chosen) == e).sum() for e in range(4)])
    assert float(stats[0]) == counts.sum()
    assert float(stats[1]) == (counts > 0).sum()
    np.testing.assert_allclose(float(stats[2]),
                               counts.max() / counts.mean(), rtol=1e-6)


def _old_rounding(rows: int) -> int:
    """What a prefill's pass held while the compiler's grouped matmul
    took it (PR 40 to PR 46): up to 512 whole tiles of 128; beyond, an
    odd multiple of 256."""
    return -(-rows // 128) * 128 if rows < 512 \
        else (rows + 255) // 512 * 512 + 256


@pytest.mark.parametrize("n_tokens,want,tall", [
    (64, 120, 8), (8192, 2304, 256), (1024, 384, 128), (5120, 1536, 256),
    (3072, 1024, 256), (2048, 768, 256), (2, 8, 8)])
def test_tile_rows_follow_the_chips_share(n_tokens, want, tall):
    """A decode wave's 16 expected rows are 32 with their margin, few a
    group: each of the 12 experts begins on a row tile of its own, 7
    rows more apiece, in whole tiles of 8 (120).  A prefill's rows lie
    end to end in whole tall tiles and no row is added for a group: an
    8k prefill's 2,228 take 2,304 in one pass, tiles of 256, of 128
    where the pass has fewer than four of those; never more rows than
    while the compiler's grouped matmul took them, and never more than
    the tokens can send (2 tokens x 8 choices: one tile)."""
    cfg = ex.ExpertsConfig(d_model=8, d_expert=8, n_routed=384, top_k=8,
                           held=ex.held_range(0, 12))
    assert ex.tile_rows(n_tokens, cfg) == want
    assert ex.row_tile(n_tokens, cfg) == tall and want % tall == 0
    assert ex.few_a_group(n_tokens, cfg) == (n_tokens <= 64)
    if n_tokens > 64:
        assert ex._local_rows(n_tokens, cfg) <= want <= _old_rounding(
            ex._local_rows(n_tokens, cfg))


@pytest.mark.parametrize("n_tokens,want", [
    (64, 2304), (512, 4096), (1024, 8192), (2048, 16384), (4096, 32768),
    (8192, 33024)])
def test_lagunas_pass_holds_no_more_rows_than_it_did(n_tokens, want):
    """Laguna-XS.2's layer (256 experts of (2,048, 512), all held, 8 a
    token; `LagunaConfig.moe_tile_rows` 33,024): a prefill bucket's
    pass is its assignments in tiles of 256, one pass up to 4,096
    tokens and two at 8,192, the first of 33,024 rows as before."""
    cfg = ex.ExpertsConfig(d_model=2048, d_expert=512, n_routed=256,
                           top_k=8, held=None, param_dtype=jnp.bfloat16,
                           tile_rows=33_024)
    assert ex.tile_rows(n_tokens, cfg) == want
    few = n_tokens == 64
    assert ex.few_a_group(n_tokens, cfg) == few
    assert ex.row_tile(n_tokens, cfg) == (8 if few else 256)
    if not few:
        assert want <= min(_old_rounding(n_tokens * 8), 33_024)


#: the serving path (`tiled=True`: the kernels of ops/moe_dispatch.py
#: around the grouped matmuls) against the path that differentiates, at
#: the shapes the cells run (cut to toy widths) and their edges.
#: name -> (N, rows that hold a token or None, held or None, tile_rows,
#: the router's bias or None, stacked)
SERVED = {
    "a_decode_wave": (64, None, (0, 1, 2, 3, 4, 5), 4096, None, False),
    "a_decode_wave_with_idle_rows": (64, "every_third_idle", (2, 3, 5, 11),
                                     4096, None, False),
    "a_prefill_bucket_with_pads": (1024, 1000, (0, 1, 2, 3, 4, 5), 4096,
                                   None, False),
    "3000_real_of_3072": (3072, 3000, (4, 5, 6, 7), 4096, None, False),
    "every_expert_held": (64, None, None, 4096, None, False),
    "every_expert_held_and_pads": (96, 80, None, 4096, None, False),
    "a_stack_of_layers": (64, 50, (0, 1, 2, 3, 4, 5), 4096, None, True),
    "one_held_expert_takes_every_local_row": (
        128, None, (3, 12, 13, 14), 4096, {3: 10.0, 12: -10.0, 13: -10.0,
                                           14: -10.0}, False),
    "no_row_is_local": (64, None, (0, 1), 4096, {0: -10.0, 1: -10.0},
                        False),
    # few rows a group: the fused kernel's regime (`few_a_group`)
    "a_short_wave_over_a_stack": (8, None, (0, 1, 2, 3, 4, 5), 4096, None,
                                  True),
    "a_short_wave_with_idle_rows": (16, "every_third_idle", None, 4096,
                                    None, False),
    "a_short_wave_on_one_expert": (20, None, (3, 12, 13, 14), 4096,
                                   {3: 10.0, 12: -10.0, 13: -10.0,
                                    14: -10.0}, True),
    "local_rows_just_under_one_tile": (40, 31, None, 128, None, False),
    "local_rows_fill_one_tile": (40, 32, None, 128, None, False),
    "local_rows_just_over_one_tile": (40, 33, None, 128, None, False),
    "local_rows_over_many_tiles": (200, None, None, 128, {3: 10.0}, False),
}


@pytest.fixture(params=["kernels_interpreted", "fused_interpreted",
                        "as_the_cpu_runs_it"])
def runs(request, monkeypatch):
    """What `routed_experts(tiled=True)` moves rows with, and what
    multiplies rows that are few a group: off the chip the kernels'
    `jnp` references; steered here to the kernels themselves, in the
    Pallas interpreter (the walks; the walks and `grouped_swiglu`)."""
    if request.param != "as_the_cpu_runs_it":
        monkeypatch.setattr(ex, "dispatch_reference", functools.partial(
            ex.moe_dispatch, interpret=True))
        monkeypatch.setattr(ex, "combine_reference", functools.partial(
            ex.moe_combine, interpret=True))
    if request.param == "fused_interpreted":
        monkeypatch.setattr(ex, "fused_reference", functools.partial(
            ex._fused, interpret=True))
    return request.param


@pytest.mark.parametrize("name", SERVED)
def test_the_served_path_is_the_one_that_differentiates(whole, name, runs):
    n, real, held, tile, bias, stacked = SERVED[name]
    _, p = whole
    cfg = _cfg(held=held, tile_rows=tile)
    x = _x(n, seed=n + len(name))
    router = p["router"]
    if bias:
        router = dict(router, bias=jnp.zeros((E,)).at[
            jnp.asarray(list(bias))].set(jnp.asarray(list(bias.values()))))
    chosen, w = ex.route(router, x, cfg)
    if real == "every_third_idle":
        valid = jnp.arange(n) % 3 != 0
    else:
        valid = None if real is None else jnp.arange(n) >= n - real
    mine = {k: v[jnp.asarray(cfg.held_ids)] for k, v in p["experts"].items()}
    layer = None
    if stacked:
        other = jax.tree.map(lambda a: a[::-1] * 0.5, mine)
        mine = jax.tree.map(lambda a, b: jnp.stack([a, b]), other, mine)
        layer = jnp.int32(1)
    base = _x(n, seed=99)
    want, want_stats = ex.routed_experts(mine, x, chosen, w, cfg, valid,
                                         tiled=False, layer=layer,
                                         base=base)
    got, stats = ex.routed_experts(mine, x, chosen, w, cfg, valid,
                                   tiled=True, layer=layer, base=base)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    # the counter is the integer the choices give
    local = np.isin(np.asarray(chosen), cfg.held_ids)
    if valid is not None:
        local &= np.asarray(valid)[:, None]
    assert float(stats[0]) == local.sum()
    if name == "no_row_is_local":
        assert local.sum() == 0
        np.testing.assert_array_equal(np.asarray(got), np.asarray(base))
    if name == "one_held_expert_takes_every_local_row":
        assert float(stats[1]) == 1 and local.sum() == n
    assert ex.few_a_group(n, cfg) == (
        name.startswith(("a_short_wave", "local_rows_"))
        and name != "local_rows_over_many_tiles")
    # the visits the kernel makes the experts' rows: the row tiles they
    # fill where every group begins on its own, else the tall tiles
    # they lie in, end to end
    per = np.asarray([(np.asarray(chosen)[local] == e).sum()
                      for e in cfg.held_ids])
    if ex.few_a_group(n, cfg):
        assert float(stats[3]) == (-(-per // ex.ROW_TILE)).sum()
    else:
        tall = ex.row_tile(n, cfg)
        ends = np.cumsum(per)
        lain = (ends - 1) // tall - (ends - per) // tall + 1
        assert float(stats[3]) == lain[per > 0].sum()
    if name.startswith("local_rows_"):
        rows = ex.tile_rows(n, cfg)
        passes = -(-int(local.sum()) // rows)
        assert passes == {"just_under_one_tile": 1, "fill_one_tile": 1,
                          "just_over_one_tile": 2,
                          "over_many_tiles": 7}[name[len("local_rows_"):]]


#: every expert held, many and small, softmax scores (the Laguna cell's
#: layer: 256 experts of width 512, 8 a token, cut here to 64 of width
#: 16): a decode wave gives most experts 0 to 3 rows and some none.
#: name -> (N, rows that hold a token or None)
MANY_SMALL = {
    "a_decode_wave": (16, None),
    "a_decode_wave_with_idle_rows": (16, "every_third_idle"),
    "one_row": (8, 1),
    "a_prefill_bucket_with_pads": (256, 200),
}


@pytest.mark.parametrize("name", MANY_SMALL)
def test_many_small_experts_all_held_with_empty_groups(name, runs):
    n, real = MANY_SMALL[name]
    many = 64
    cfg = _cfg(n_routed=many, top_k=8, scoring="softmax", route_scale=2.5)
    assert cfg.n_held == many and cfg.held is None
    p = ex.experts_init(jax.random.PRNGKey(2), cfg, std=0.3)
    x = _x(n, seed=n + len(name))
    chosen, w = ex.route(p["router"], x, cfg)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 2.5, rtol=1e-5)
    if real == "every_third_idle":
        valid = jnp.arange(n) % 3 != 0
    else:
        valid = None if real is None else jnp.arange(n) >= n - real
    base = _x(n, seed=98)
    want, want_stats = ex.routed_experts(p["experts"], x, chosen, w, cfg,
                                         valid, tiled=False, base=base)
    got, stats = ex.routed_experts(p["experts"], x, chosen, w, cfg, valid,
                                   tiled=True, base=base)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(want_stats))
    rows = n if valid is None else int(np.asarray(valid).sum())
    assert float(stats[0]) == rows * 8            # every choice is local
    if n <= 16:
        assert float(stats[1]) < many             # groups of 0 rows
        # one pass takes every assignment the wave can make, each
        # expert's on a row tile of its own
        assert ex.few_a_group(n, cfg)
        assert ex.tile_rows(n, cfg) == n * 8 + (ex.ROW_TILE - 1) * many
    if valid is None:
        dense = _dense_reference(p, x, cfg) + base
        np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                                   atol=3e-5)


@pytest.mark.parametrize("bad", [{"held": (0, 0)}, {"held": (99,)},
                                 {"top_k": 0}, {"scoring": "tanh"}])
def test_a_wrong_configuration_is_refused(bad):
    with pytest.raises(ValueError):
        _cfg(**bad)


def test_the_layer_differentiates_untiled(whole):
    cfg, p = whole
    x = _x(16)
    g = jax.grad(lambda q: jnp.sum(ex.moe_layer(q, x, cfg,
                                                tiled=False)[0] ** 2))(p)
    assert float(jnp.abs(g["experts"]["w_down"]).max()) > 0
    assert ex.experts_param_count(cfg) == sum(
        a.size for a in jax.tree.leaves(p))
