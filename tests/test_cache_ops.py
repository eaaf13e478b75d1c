"""The cache's format has one owner: the operations of
models/decode_common.py that the serving engine moves rows, blocks and
recurrent state with, on the caches of the three families at nano size
(gpt2: one K/V head per query head; llama: grouped queries, fewer K/V
heads; jamba: K/V of its attention layers only, beside per-slot state
and a snapshot pool)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import decode_common as dc  # noqa: E402
from ray_tpu.models.families import family  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}
FAMILIES = ("gpt2", "llama", "jamba")
SLOTS, BLOCKS, BS = 3, 7, 16


def _cfg(name):
    return family(name).config("nano", **_OVR)


def _noise(cache, seed=0):
    """The cache with every K/V and state tensor filled with distinct
    values (the engine's programs would have written them)."""
    rng = np.random.default_rng(seed)
    return {k: (v if v.dtype == jnp.int32 else jnp.asarray(
        rng.standard_normal(v.shape), v.dtype))
        for k, v in cache.items()}


def _paged(name, seed=0):
    fam, cfg = family(name), _cfg(name)
    return _noise(fam.init_paged_cache(cfg, SLOTS, num_blocks=BLOCKS,
                                       block_size=BS), seed), cfg


def _same(a, b, but=()):
    assert a.keys() == b.keys()
    for k in a:
        if k not in but:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


@pytest.mark.parametrize("name", FAMILIES)
def test_block_bytes_is_the_engines_old_formula(name):
    """What the pager is told a block weighs, read off the cache, equals
    what the engine used to compute from the config's attributes."""
    cache, cfg = _paged(name)
    kv_heads = getattr(cfg, "n_kv_head", None) or cfg.n_head
    old = (2 * getattr(cfg, "n_kv_layer", cfg.n_layer) * BS * kv_heads
           * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize)
    assert dc.block_bytes(cache) == old
    rows = dc.block_rows(cache, 5)
    assert rows.shape == (5,) + cache["k"][:, 0].shape
    assert rows.dtype == cache["k"].dtype
    state = [n for n in cache if n.endswith(("conv", "ssm"))]
    assert dc.state_bytes(cache) == sum(cache[n].nbytes for n in state)
    assert (dc.state_bytes(cache) > 0) == (name == "jamba")
    assert dc.kv_shards(cache) == 1


@pytest.mark.parametrize("name", FAMILIES)
def test_admit_lands_one_row_and_only_it(name):
    fam, cfg = family(name), _cfg(name)
    pool = _noise(fam.init_cache(cfg, SLOTS), 1)
    row = _noise(fam.init_cache(cfg, 1), 2)
    row["pos"], row["start"] = jnp.asarray([9]), jnp.asarray([4])
    out = jax.jit(dc.admit)(pool, row, np.int32(1))
    for k, v in out.items():
        axis = 2 if k == "conv" else 0 if v.ndim == 1 else 1
        np.testing.assert_array_equal(
            np.take(v, [1], axis=axis), row[k], err_msg=k)
        np.testing.assert_array_equal(
            np.take(v, [0, 2], axis=axis),
            np.take(pool[k], [0, 2], axis=axis), err_msg=k)


@pytest.mark.parametrize("name", FAMILIES)
def test_clear_row_parks_the_row_at_the_null_block(name):
    cache, _ = _paged(name)
    cache["block_tables"] = cache["block_tables"] + 3
    cache["pos"] = cache["pos"] + 11
    out = dc.clear_row(cache, np.int32(2))
    assert not np.asarray(out["block_tables"][2]).any()
    assert int(out["pos"][2]) == 0
    np.testing.assert_array_equal(out["block_tables"][:2], 3)
    np.testing.assert_array_equal(out["pos"][:2], 11)
    _same(out, cache, but=("block_tables", "pos"))


@pytest.mark.parametrize("name", FAMILIES)
def test_saved_blocks_install_back_bit_exactly(name):
    """install_blocks ∘ save_block: a spilled block restored into
    another block id is the same bytes, the pad entries land in the
    null block, and nothing else moves."""
    cache, _ = _paged(name)
    rows = dc.block_rows(cache, 4)
    ks, vs = np.zeros(rows.shape, rows.dtype), np.zeros(rows.shape,
                                                        rows.dtype)
    ks[0], vs[0] = dc.save_block(cache, np.int32(5))
    ks[1], vs[1] = dc.save_block(cache, np.int32(2))
    ids = jnp.asarray([3, 6, 0, 0], jnp.int32)
    out = jax.jit(dc.install_blocks)(cache, ids, jnp.asarray(ks),
                                     jnp.asarray(vs))
    for kv in ("k", "v"):
        np.testing.assert_array_equal(out[kv][:, 3], cache[kv][:, 5])
        np.testing.assert_array_equal(out[kv][:, 6], cache[kv][:, 2])
        np.testing.assert_array_equal(out[kv][:, [1, 2, 4, 5]],
                                      cache[kv][:, [1, 2, 4, 5]])
        assert not np.asarray(out[kv][:, 0]).any()     # the pads
    _same(out, cache, but=("k", "v"))


@pytest.mark.parametrize("name", FAMILIES)
def test_a_handoff_round_trips_bit_exactly(name):
    """kv_handoff_install ∘ kv_handoff_export between two pools: the
    receiving row reads exactly the rows the sending one wrote, and is
    pointed at them."""
    sender, _ = _paged(name, 3)
    receiver, cfg = _paged(name, 4)
    n = cfg.max_seq // BS
    src = np.zeros((n,), np.int32)
    dst = np.zeros((n,), np.int32)
    src[:2], dst[:2] = (4, 1), (2, 6)
    ks, vs = jax.jit(dc.kv_handoff_export)(sender, jnp.asarray(src))
    assert ks.shape == dc.block_rows(sender, n).shape
    row_bt = jnp.asarray(dst)
    out = jax.jit(dc.kv_handoff_install)(
        receiver, jnp.asarray(dst), ks, vs, np.int32(1), row_bt,
        np.int32(27))
    for kv in ("k", "v"):
        np.testing.assert_array_equal(out[kv][:, 2], sender[kv][:, 4])
        np.testing.assert_array_equal(out[kv][:, 6], sender[kv][:, 1])
        np.testing.assert_array_equal(out[kv][:, [1, 3, 4, 5]],
                                      receiver[kv][:, [1, 3, 4, 5]])
    np.testing.assert_array_equal(out["block_tables"][1], dst)
    assert (int(out["pos"][1]), int(out["start"][1])) == (27, 0)
    _same(out, receiver, but=("k", "v", "block_tables", "pos", "start"))


def test_restore_state_moves_snapshot_rows_only():
    """A slot's recurrent state becomes one snapshot entry's; the K/V
    pool, the snapshots and the other slots' state stay."""
    cache, _ = _paged("jamba")
    out = jax.jit(dc.restore_state)(cache, np.int32(2), np.int32(0))
    np.testing.assert_array_equal(out["ssm"][:, 0],
                                  cache["snap_ssm"][:, 2])
    np.testing.assert_array_equal(out["conv"][:, :, 0],
                                  cache["snap_conv"][:, :, 2])
    np.testing.assert_array_equal(out["ssm"][:, 1:], cache["ssm"][:, 1:])
    np.testing.assert_array_equal(out["conv"][:, :, 1:],
                                  cache["conv"][:, :, 1:])
    _same(out, cache, but=("ssm", "conv"))


@pytest.mark.parametrize("name", FAMILIES)
def test_logical_axes_cover_the_cache(name):
    """One entry a tensor, as long as the tensor has axes; only K/V
    name a sharded axis (the heads, at index 3 in both layouts)."""
    cache, _ = _paged(name)
    axes = dc.cache_logical_axes(cache)
    assert axes.keys() == cache.keys()
    for k, v in cache.items():
        assert len(axes[k]) == v.ndim
        named = [a for a in axes[k] if a is not None]
        assert named == (["heads", "head_dim"] if k in ("k", "v") else [])
