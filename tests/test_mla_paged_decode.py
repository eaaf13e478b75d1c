"""The paged latent-attention decode kernel (ray_tpu/ops/mla_paged_decode.py)
on the CPU, in the Pallas interpreter, against `attend_absorbed` over
the gathered view; and which programs take it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import scopes
from ray_tpu.models import decode_common as dc
from ray_tpu.models import experts
from ray_tpu.models import kimi_k2 as K
from ray_tpu.models import kimi_k2_decode as D
from ray_tpu.models.glm_dsa_decode import _selected_rows
from ray_tpu.ops import dsa
from ray_tpu.ops.mla_paged_decode import (mla_paged_decode,
                                          mla_paged_decode_reference,
                                          rotary_lanes,
                                          rotary_lanes_reference)
from tests.test_mla import BF16_RMS, BF16_TOKEN_MEDIAN, F32_ATOL
from tests.test_ssm_scan import _count

BS = 16
_OVR = {"held": (0, 1, 2, 3, 4, 5)}


@pytest.fixture
def interpreted(monkeypatch):
    """The decode step takes the kernel's path (the backend test says
    "tpu") and the kernel runs in the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(D, "mla_paged_decode", functools.partial(
        mla_paged_decode, interpret=True))


def _tables(rng, kind, rows, nb, blocks):
    """Block tables (rows, nb) over a pool of `blocks` (block 0 is the
    null block and is never a row's)."""
    if kind == "in_order":
        return 1 + np.arange(rows * nb).reshape(rows, nb) % (blocks - 1)
    tables = np.stack([1 + rng.permutation(blocks - 1)[:nb]
                       for _ in range(rows)])      # out of order
    if kind == "shared":     # a prefix's blocks resident for every row
        tables[:, :3] = tables[0, :3]
    return tables


# one wave each: the rows' lengths, the tables' kind, max_seq
WAVES = {
    "ragged": ([1, 16, 17, 512, 513, 0, 100, 300], "out_of_order", 1024),
    # the last: a row stepped past its table's end (a wave queued behind
    # the row's last): every slot attended, none past the table walked
    "block_edges_shared": ([16, 32, 48, 33, 15, 64, 133], "shared", 128),
    "every_row_idle": ([0, 0, 0], "in_order", 128),
    "one_past_a_chunk": ([1024, 1025, 1023, 2047], "shared", 2048),
    "full_table": ([8704, 8703, 0, 8689], "out_of_order", 8704),
}


def _wave(name, dtype, seed=0):
    """A paged cache with random pools, one decode column's attention
    inputs, and the layer's attention weights."""
    lengths, kind, max_seq = WAVES[name]
    cfg = K.kimi_k2_config("nano", dtype=dtype, max_seq=max_seq, **_OVR)
    rng = np.random.default_rng(seed)
    B, nb = len(lengths), max_seq // BS
    blocks = (2 if kind == "in_order" else 1) * nb + 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    cache = D.kimi_k2_init_paged_cache(cfg, B, num_blocks=blocks,
                                       block_size=BS)
    for name_, k in zip(("ckv", "kpe"), ks):
        cache[name_] = jax.random.normal(k, cache[name_].shape,
                                         jnp.float32).astype(dtype)
    cache["block_tables"] = jnp.asarray(
        _tables(rng, kind, B, nb, blocks), jnp.int32)
    cache["pos"] = jnp.asarray(lengths, jnp.int32)
    q = jax.random.normal(ks[2], (B, 1, cfg.n_head, cfg.qk_head_dim),
                          jnp.float32).astype(dtype)
    fresh = (jax.random.normal(ks[3], (B, 1, cfg.kv_lora_rank)
                               ).astype(dtype),
             jax.random.normal(ks[4], (B, 1, cfg.qk_rope_dim)
                               ).astype(dtype))
    params = K.kimi_k2_init(jax.random.PRNGKey(1), cfg)
    p = jax.tree.map(lambda a: a[0], params["moe"]["attn"])
    return cfg, cache, q, fresh, p


def _over_the_view(cfg, cache, q, fresh, p, lidx):
    """`attend_absorbed` as the paged decode step calls it off the
    chip: the views gathered by block table, the slots under `pos`."""
    B, nb = cache["block_tables"].shape
    views = [cache[n][lidx][cache["block_tables"]].reshape(B, nb * BS, -1)
             for n in ("ckv", "kpe")]
    mask = dc.slot_mask(cache["start"], cache["pos"], cfg.max_seq)[:, None]
    return K.attend_absorbed(q, *views, p, mask, cfg, fresh=fresh)


def _through_the_kernel(cfg, cache, q, fresh, p, lidx):
    return D.attend_paged(q, cache["ckv"], rotary_lanes(cache["kpe"]),
                          cache, lidx, p, fresh, cfg)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("wave", [w for w in WAVES
                                  if w != "every_row_idle"])
def test_a_wave_through_the_kernel_is_the_gathered_views(wave, dtype,
                                                         interpreted):
    """Rows of length 1, on a block's and a chunk's edge and one past
    it, the whole table, rows without a sequence, each with its fresh
    row, over tables out of order and shared between rows, in the last
    layer of the pool."""
    cfg, cache, q, fresh, p = _wave(wave, dtype)
    lidx = jnp.int32(cfg.n_layer - 1)
    got = np.asarray(_through_the_kernel(cfg, cache, q, fresh, p, lidx),
                     np.float32)
    assert got.shape == (len(WAVES[wave][0]), 1, cfg.n_head,
                         cfg.v_head_dim)
    if dtype == jnp.float32:
        want = np.asarray(_over_the_view(cfg, cache, q, fresh, p, lidx))
        np.testing.assert_allclose(got, want, atol=F32_ATOL)
        return
    # bf16 against the same inputs attended in float32: the tolerance
    # tests/test_mla.py states for bf16 compute, on outputs of std ~0.4
    f32 = K.kimi_k2_config("nano", dtype=jnp.float32,
                           max_seq=cfg.max_seq, **_OVR)
    up = lambda t: jax.tree.map(  # noqa: E731
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        t)
    want = np.asarray(_over_the_view(f32, up(cache), up(q), up(fresh),
                                     up(p), lidx))
    err = np.abs(got - want)
    assert np.sqrt(np.mean(err ** 2)) < BF16_RMS
    assert np.median(err.reshape(len(err), -1).max(-1)) \
        < BF16_TOKEN_MEDIAN


def test_a_row_without_a_sequence_returns_its_fresh_latent(interpreted):
    """``pos == 0``: nothing walked, the fresh key's weight is 1, as the
    masked path has it."""
    cfg, cache, q, fresh, p = _wave("every_row_idle", jnp.float32)
    got = _through_the_kernel(cfg, cache, q, fresh, p, jnp.int32(0))
    want = jnp.einsum("btc,chv->bthv", fresh[0], p["wv_b"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_ATOL)


def test_the_kernel_honours_a_first_slot():
    """`start` is 0 for every row of a paged cache today; the kernel
    masks the slots before it all the same, as `slot_mask` does."""
    cfg, cache, q, fresh, p = _wave("ragged", jnp.float32)
    start = jnp.asarray([0, 3, 16, 500, 17, 0, 99, 1], jnp.int32)
    kq, kr = jax.random.split(jax.random.PRNGKey(9))
    args = (jax.random.normal(kq, (8, cfg.n_head, cfg.kv_lora_rank)),
            jax.random.normal(kr, (8, cfg.n_head, cfg.qk_rope_dim)),
            cache["ckv"])
    rest = (cache["block_tables"], cache["pos"], jnp.int32(1),
            (fresh[0][:, 0], fresh[1][:, 0]))
    got = mla_paged_decode(*args, rotary_lanes(cache["kpe"]), *rest,
                           scale=0.3, start=start, interpret=True)
    want = mla_paged_decode_reference(*args, cache["kpe"], *rest,
                                      scale=0.3, start=start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_ATOL)
    moved = mla_paged_decode_reference(*args, cache["kpe"], *rest,
                                       scale=0.3)
    assert np.abs(np.asarray(moved) - np.asarray(want)).max() > 1e-3


# -- the walk under a selection ------------------------------------------------

#: a pool of 8 blocks makes a chunk 8 blocks (128 slots) and a table of
#: 20 two chunks and a half: the last is partly past the table.  Rows:
#: (pos, start); the scores are a case's own
CHUNK_SLOTS, TABLE = 8 * BS, 20
SELECTIONS = {
    # the own position scores lowest in rows 0 and 2, highest in row 1
    "the_own_position_left_out": ([(200, 0), (300, 0), (130, 0)], 24),
    # nothing of a row's first chunk (rows 0, 1) or first two (row 2)
    # is selected, nor its own position: the sums open on exp(0) terms
    "a_first_chunk_with_no_selected_slot": (
        [(200, 0), (319, 0), (300, 0)], 24),
    # the mask is every reachable slot: the walk without a mask
    "a_context_within_topk": ([(23, 0), (9, 0), (1, 0)], 24),
    # scores of four values: the last place is shared by dozens
    "tied_at_the_last_place": ([(200, 0), (300, 0), (77, 0)], 24),
    "an_idle_row_and_a_first_slot": ([(0, 0), (250, 37), (140, 130)], 24),
    # rows that reach the table's end, whose last chunk is half null
    "a_last_chunk_partly_past_the_table": (
        [(320, 0), (319, 0), (257, 0)], 40),
}


def _scores(case, rows, S, rng):
    scores = rng.standard_normal((len(rows), S)).astype(np.float32)
    at = np.arange(len(rows)), [pos for pos, _ in rows]
    if case == "the_own_position_left_out":
        scores[at] = [-9.0, 9.0, -9.0]
    elif case == "a_first_chunk_with_no_selected_slot":
        scores[:, :CHUNK_SLOTS] = -9.0
        scores[2, :2 * CHUNK_SLOTS] = -9.0
        scores[at] = -9.0
    elif case == "tied_at_the_last_place":
        scores = rng.integers(0, 4, scores.shape).astype(np.float32)
    return jnp.asarray(scores)


@pytest.fixture(scope="module")
def pools():
    """Two layers' pools of 8 blocks, three rows' queries and new rows,
    a layer's two absorbed products."""
    cfg = K.kimi_k2_config("nano", dtype=jnp.float32, max_seq=TABLE * BS,
                           **_OVR)
    c, r, H = cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.n_head
    ks = jax.random.split(jax.random.PRNGKey(5), 7)
    draw = lambda k, *shape: jax.random.normal(k, shape)  # noqa: E731
    return (cfg, draw(ks[0], 2, 8, BS, c), draw(ks[1], 2, 8, BS, r),
            draw(ks[2], 3, 1, H, cfg.qk_head_dim),
            (draw(ks[3], 3, 1, c), draw(ks[4], 3, 1, r)),
            {"wk_b": draw(ks[5], c, H, cfg.qk_nope_dim) * 0.2,
             "wv_b": draw(ks[6], c, H, cfg.v_head_dim) * 0.2})


@pytest.mark.parametrize("case", SELECTIONS)
def test_the_walk_under_a_selection_is_the_gathered_selection(case, pools,
                                                              interpreted):
    """`mla_paged_decode` with a mask (`dsa.select_mask`) against the
    ``jnp`` path of a GLM-5 decode step, `dsa.select_top`'s positions
    gathered by (block table, offset) and attended absorbed, on the
    same pools and scores: the two forms select the same set, and a
    slot not selected weighs exactly nothing."""
    rows, topk = SELECTIONS[case]
    cfg, ckv, kpe, q, fresh, p = pools
    rng = np.random.default_rng(5)
    B, S, lidx = len(rows), TABLE * BS, 1
    cache = {"block_tables": jnp.asarray(_tables(rng, "in_order", B, TABLE,
                                                 8), jnp.int32),
             "pos": jnp.asarray([pos for pos, _ in rows], jnp.int32),
             "start": jnp.asarray([lo for _, lo in rows], jnp.int32)}
    scores = _scores(case, rows, S, rng)

    @jax.jit
    def gathered(cache, scores):
        ok = dc.slot_mask(cache["start"], cache["pos"] + 1, S)
        idx, valid = dsa.select_top(scores, ok, topk)
        own = idx == cache["pos"][:, None]
        picked = (_selected_rows(dsa.pool_rows(pool), lidx,
                                 cache["block_tables"], idx, BS, own, new)
                  for pool, new in zip((ckv, kpe), fresh))
        return K.attend_absorbed(q, *picked, p, valid[:, None], cfg), \
            dsa.select_mask(scores, ok, topk), idx, valid, own & valid

    want, mask, idx, valid, own = gathered(cache, scores)
    # the selected sets of the two forms are one set
    picked = np.zeros((B, S), bool)
    np.put_along_axis(picked, np.asarray(idx), np.asarray(valid), axis=1)
    np.testing.assert_array_equal(np.asarray(mask), picked)
    if case == "a_first_chunk_with_no_selected_slot":
        assert not picked[:, :CHUNK_SLOTS].any() and not own.any()
    walk = functools.partial(D.attend_paged, q, ckv, rotary_lanes(kpe),
                             cache, lidx, p, fresh, cfg)
    got = walk(selected=mask)
    if case == "a_context_within_topk":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(walk()))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_ATOL)
    held = mla_paged_decode_reference(
        jnp.einsum("bhn,chn->bhc", q[:, 0, :, :cfg.qk_nope_dim], p["wk_b"]),
        q[:, 0, :, cfg.qk_nope_dim:], ckv, kpe, cache["block_tables"],
        cache["pos"], lidx, (fresh[0][:, 0], fresh[1][:, 0]),
        scale=K.softmax_scale(cfg), start=cache["start"], selected=mask)
    np.testing.assert_allclose(
        np.asarray(jnp.einsum("bhc,chv->bhv", held, p["wv_b"])),
        np.asarray(want)[:, 0], atol=F32_ATOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("r,L,blocks,lanes", [
    (8, 3, 20, 128), (64, 6, 20, 384), (64, 2, 300, 128), (256, 3, 5, 768),
    (64, 5, 20, 384), (64, 1, 130, 128)],
    ids=["nano", "published", "a_ragged_last_tile", "whole_tiles",
         "five_layers_leave_half_a_tile", "one_layer_leaves_half_a_tile"])
def test_rotary_lanes_lays_the_layers_side_by_side(r, L, blocks, lanes,
                                                   dtype, monkeypatch):
    """All layers' keys of a position along the lanes, whole lane
    tiles, zeros where the layers leave the last one part empty: the
    kernel (interpreted) where a layer's keys are half a tile or whole
    ones, 128 blocks a grid step and the last tile ragged; the ``jnp``
    transposes where they are narrower.  A width that neither divides a
    tile nor fills whole ones is refused: no layer's keys would lie in
    one tile."""
    kpe = jax.random.normal(jax.random.PRNGKey(0), (L, blocks, BS, r)
                            ).astype(dtype)
    if r >= 64:         # through the kernel, not the fallback
        from ray_tpu.ops import mla_paged_decode as module
        monkeypatch.setattr(module, "rotary_lanes_reference", None)
    out = rotary_lanes(kpe, interpret=True)
    monkeypatch.undo()
    assert out.shape == (blocks, BS, lanes) and out.dtype == dtype
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(
        rotary_lanes_reference(kpe), np.float32))
    for layer in range(L):
        np.testing.assert_array_equal(
            np.asarray(out[..., layer * r:(layer + 1) * r], np.float32),
            np.asarray(kpe[layer], np.float32))
    assert not np.asarray(out[..., L * r:], np.float32).any()
    with pytest.raises(ValueError, match="96 wide"):
        rotary_lanes(jnp.zeros((L, blocks, BS, 96), dtype))


# -- which programs take the kernel ------------------------------------------

def _programs(cfg, params):
    paged = D.kimi_k2_init_paged_cache(cfg, 3, num_blocks=17,
                                       block_size=BS)
    return {
        "paged_decode": (
            lambda c, t: D.kimi_k2_decode_step(params, c, t, cfg),
            (paged, jnp.ones((3,), jnp.int32))),
        "dense_decode": (
            lambda c, t: D.kimi_k2_decode_step(params, c, t, cfg),
            (D.kimi_k2_init_cache(cfg, 3), jnp.ones((3,), jnp.int32))),
        "paged_prefill": (
            lambda c, t: D.kimi_k2_paged_prefill(
                params, c, t, cfg, prefix_len=0, n_tail=20, slot=1,
                row_bt=jnp.zeros((cfg.max_seq // BS,), jnp.int32)),
            (paged, jnp.ones((1, 32), jnp.int32))),
    }


@pytest.fixture(scope="module")
def tiny():
    cfg = K.kimi_k2_config("nano", dtype=jnp.float32, **_OVR)
    return cfg, K.kimi_k2_init(jax.random.PRNGKey(0), cfg)


def _named(jaxpr, kernel):
    """``pallas_call``s named `kernel` in a jaxpr, its sub-jaxprs'
    too."""
    return sum((eqn.primitive.name == "pallas_call"
                and eqn.params["name"] == kernel)
               + sum(_named(sub, kernel)
                     for sub in jax.core.jaxprs_in_params(eqn.params))
               for eqn in jaxpr.eqns)


@pytest.mark.parametrize("backend,program,kernels", [
    ("cpu", "paged_decode", 0), ("tpu", "paged_decode", 2),
    ("tpu", "dense_decode", 0), ("tpu", "paged_prefill", 0)])
def test_only_the_paged_decode_step_on_the_chip_holds_the_kernel(
        tiny, monkeypatch, backend, program, kernels):
    """A paged cache, one column a row and the TPU backend take the
    kernel, one ``pallas_call`` in each of the two scans over layers;
    the CPU, the dense cache and a prefill keep the ``jnp`` paths (the
    expert layer's own kernels, which every program on the chip holds,
    are tests/test_kimi_k2_scopes.py's: the two that move rows and,
    these programs' rows being few an expert, ``grouped_swiglu``)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    fn, args = _programs(*tiny)[program]
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _named(jaxpr, scopes.MLA_PAGED_DECODE) == kernels
    assert _count(jaxpr, "pallas_call") == (
        kernels + 3 if backend == "tpu" else 0)
    assert _named(jaxpr, scopes.GROUPED_SWIGLU) == (backend == "tpu")


def _shapes(jaxpr, found=None):
    """The shapes of every value a jaxpr computes, its sub-jaxprs'
    too."""
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.update(tuple(v.aval.shape) for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _shapes(sub, found)
    return found


def test_on_the_kernels_path_no_view_is_gathered(tiny, monkeypatch):
    """The ``jnp`` path gathers every row's table to the
    dense-equivalent (rows, max_seq, width) views; the kernel's path
    computes nothing of that size."""
    cfg, _ = tiny
    fn, args = _programs(*tiny)["paged_decode"]
    views = {(3, cfg.max_seq, cfg.kv_lora_rank),
             (3, cfg.max_seq, cfg.qk_rope_dim)}
    assert views <= _shapes(jax.make_jaxpr(fn)(*args).jaxpr)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = _programs(*tiny)["paged_decode"]    # a trace of its own
    assert not views & _shapes(jax.make_jaxpr(fn)(*args).jaxpr)


def test_the_decode_step_through_the_kernel_is_the_jnp_step(tiny,
                                                            monkeypatch):
    """Prefill two rows into the pool, leave one idle, then decode
    steps by both paths: the same logits, the same cache."""
    cfg, params = tiny
    cache = D.kimi_k2_init_paged_cache(cfg, 3, num_blocks=20,
                                       block_size=BS)
    rng = np.random.RandomState(3)
    for slot, n in ((0, 21), (2, 40)):
        toks = np.zeros((1, 48), np.int32)
        toks[0, 48 - n:] = rng.randint(2, 500, n)
        row_bt = np.zeros((cfg.max_seq // BS,), np.int32)
        row_bt[:4] = 1 + 4 * slot + np.arange(4)[::-1]    # out of order
        _, cache = D.kimi_k2_paged_prefill(
            params, cache, jnp.asarray(toks), cfg,
            row_bt=jnp.asarray(row_bt), prefix_len=0, n_tail=n, slot=slot)
    assert cache["pos"].tolist() == [21, 0, 40]
    tokens = jnp.asarray([5, 0, 7], jnp.int32)
    step = lambda c: D.kimi_k2_decode_step(params, c, tokens, cfg)  # noqa: E731
    want_logits, want = step(cache)
    want_logits2, want2 = step(want)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(D, "mla_paged_decode", functools.partial(
        mla_paged_decode, interpret=True))
    # the chip's step moves the experts' rows by kernels too, and
    # multiplies them by one
    for kernel in ("moe_dispatch", "moe_combine", "_fused"):
        monkeypatch.setattr(experts, kernel, functools.partial(
            getattr(experts, kernel), interpret=True))
    got_logits, got = step(cache)
    got_logits2, got2 = step(got)
    live = np.asarray([0, 2])
    for g, w in ((got_logits, want_logits), (got_logits2, want_logits2)):
        np.testing.assert_allclose(np.asarray(g)[live], np.asarray(w)[live],
                                   atol=F32_ATOL)
    assert got2["pos"].tolist() == [23, 0, 42]
    for name in ("ckv", "kpe"):
        np.testing.assert_allclose(np.asarray(got2[name]),
                                   np.asarray(want2[name]), atol=F32_ATOL)
