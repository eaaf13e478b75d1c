"""ops/banded_flash.py: a prefill's banded flash kernel, in the Pallas
interpreter, held to the `jnp` walk it replaces on the chip
(models/banded_attention.banded_walk); the choice between the two paths;
and what the three families that share it count of it for the engine.

Heads of 128 lanes (the kernel takes no other) at toy lengths: tiles
of 32.  Every place of a geometry shares ONE traced kernel: the band
(`first`, `last`) is data.
"""

import asyncio
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import banded_attention as m
from ray_tpu.models.families import family
from ray_tpu.ops import banded_flash as flash
from ray_tpu.serve.llm import build_llm_deployment

T, HD, TILE, WINDOW = 64, 128, 32, 32

#: name -> (rows of the view, prefix_len, n_tail, window or None): where
#: the T columns stand, as `prefill_reach` lays them
PLACES = {
    "causal_triangle": (64, 0, 64, None),
    "triangle_behind_a_prefix": (128, 50, 64, None),
    "left_pads": (128, 0, 27, None),              # a whole tile of pads
    "band": (WINDOW + T, 0, 64, WINDOW),
    "band_of_a_padded_tail": (WINDOW + T, 0, 51, WINDOW),
    "band_that_starts_inside_the_prefix": (WINDOW + T, 40, 9, WINDOW),
}

#: name -> (query heads, K/V heads, scale or None, dtype)
GEOMETRIES = {
    "group_of_4": (8, 2, None, jnp.bfloat16),
    "group_of_6": (12, 2, None, jnp.bfloat16),
    "group_of_8": (16, 2, None, jnp.bfloat16),
    "group_of_6_float32": (12, 2, None, jnp.float32),
    # Phi-4-mini-flash's pair-heads: 4 query heads a K/V pair-head of
    # 2 x 64 lanes, the scale the sub-heads' 64 dims give
    "pair_heads": (8, 2, 1.0 / math.sqrt(64), jnp.bfloat16),
    # multi-head attention (models/olmo_hybrid.py): every query head
    # its own K/V head, a tile of block_q x 1 head
    "group_of_1": (3, 3, None, jnp.bfloat16),
}


def _problem(geometry, S):
    H, n_kv, scale, dtype = GEOMETRIES[geometry]
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (T, H, HD)).astype(dtype)
    k = jax.random.normal(ks[1], (S, n_kv * HD)).astype(dtype)
    v = jax.random.normal(ks[2], (S, n_kv * HD)).astype(dtype)
    cfg = types.SimpleNamespace(dtype=dtype, attn_block=TILE,
                                n_kv_head=n_kv, head_dim=HD)
    return q, k, v, cfg, scale


@pytest.mark.parametrize("place", PLACES)
@pytest.mark.parametrize("geometry", GEOMETRIES)
def test_the_kernel_is_the_jnp_walk(geometry, place):
    S, prefix_len, n_tail, window = PLACES[place]
    q, k, v, cfg, scale = _problem(geometry, S)
    first, last = (jnp.asarray(a) for a in m.prefill_reach(
        T, prefix_len, n_tail, window, xp=np))
    want = m.banded_walk(q, k, v, first, last, cfg, "attn_full", scale)
    got = flash.banded_flash(
        q, k, v, first, last, n_kv_head=cfg.n_kv_head, head_dim=HD,
        scale=1.0 / math.sqrt(HD) if scale is None else scale,
        block_q=TILE, block_k=TILE, interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 2e-5 if cfg.dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    pads = T - n_tail
    assert not np.asarray(got[:pads], np.float32).any()
    assert np.asarray(got[pads:], np.float32).any()


def test_the_walk_is_the_jnp_loops_bounds():
    """A band costs its width, a triangle its half, a tile of pads
    nothing; only the tiles an edge runs through build a mask."""
    first, last = m.prefill_reach(T, 0, T, None, xp=np)
    lo, hi, flo, fhi = flash.walk(first, last, 64, TILE, TILE)
    assert (lo.tolist(), hi.tolist()) == ([0, 0], [1, 2])
    assert (flo.tolist(), fhi.tolist()) == ([0, 0], [0, 1])
    first, last = m.prefill_reach(T, 0, 27, None, xp=np)
    lo, hi, _, _ = flash.walk(first, last, 128, TILE, TILE)
    assert (hi - lo).tolist() == [0, 1]
    first, last = m.prefill_reach(256, 300, 256, 64, xp=np)
    lo, hi, flo, fhi = flash.walk(first, last, 64 + 256, 32, 32)
    assert set((hi - lo).tolist()) == {3}      # 64 + 32 - 1 keys a tile
    assert set((fhi - flo).tolist()) == {1}


def test_shapes_the_tiles_do_not_divide_are_refused():
    assert flash.fits(8192, 8704, 48, 8, 128, 1024)
    assert flash.fits(4096, 4864, 40, 10, 128, 1280)
    assert flash.fits(1024, 512 + 1024, 64, 8, 128, 1024)
    assert flash.fits(6144, 6656, 30, 30, 128, 3840)      # a group of 1
    assert not flash.fits(8192, 8704, 48, 8, 64, 512)     # half a lane row
    assert not flash.fits(8192, 8704, 48, 8, 128, 2048)   # wider rows
    assert not flash.fits(1000, 8704, 48, 8, 128, 1024)
    assert not flash.fits(1024, 8700, 48, 8, 128, 1024)
    assert not flash.fits(0, 8704, 48, 8, 128, 1024)
    x = jnp.zeros((48, 4, HD))
    with pytest.raises(ValueError, match="whole tiles"):
        flash.banded_flash(x, x[:, 0, :], x[:, 0, :], jnp.zeros(48, int),
                           jnp.zeros(48, int), n_kv_head=1, head_dim=HD,
                           scale=1.0, block_q=TILE, block_k=TILE,
                           interpret=True)


@pytest.mark.parametrize("backend,t,kernel", [
    ("cpu", 256, False), ("tpu", 256, True), ("tpu", 250, False)],
    ids=["off_the_chip", "whole_tiles_on_the_chip", "a_bucket_they_cut"])
def test_the_path_is_picked_from_backend_and_shape(monkeypatch, backend, t,
                                                   kernel):
    """`attend_banded` asks what `_takes_kernel` asks: the chip and a
    bucket the tiles divide take the kernel, under the caller's scope
    and with its scale; anything else the `jnp` walk."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    calls = []
    real = flash.banded_flash

    def interpreted(*a, **kw):
        calls.append(kw)
        return real(*a, interpret=True, **kw)

    monkeypatch.setattr(flash, "banded_flash", interpreted)
    H, n_kv = 8, 2
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (t, H, HD), jnp.float32)
    k = jax.random.normal(ks[1], (t, n_kv * HD), jnp.float32)
    v = jax.random.normal(ks[2], (t, n_kv * HD), jnp.float32)
    cfg = types.SimpleNamespace(dtype=jnp.float32, attn_block=TILE,
                                n_kv_head=n_kv, head_dim=HD)
    first, last = (jnp.asarray(a) for a in m.prefill_reach(
        t, 0, t - 5, None, xp=np))
    got = m.attend_banded(q, k, v, first, last, cfg, "attn_full", 0.125)
    want = m.banded_walk(q, k, v, first, last, cfg, "attn_full", 0.125)
    assert bool(calls) is kernel
    if kernel:
        assert calls[0] == {"n_kv_head": n_kv, "head_dim": HD,
                            "scale": 0.125}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# -- what the engine counts ---------------------------------------------------

#: family -> (the cell's overrides that matter here, attention layers as
#: (how many, window or None))
_CELLS = {
    "laguna": (dict(max_seq=8704), [(2, None), (3, 512)]),
    "solar_open2": (dict(max_seq=8704), [(1, None)]),
    "phi4flash": (dict(max_seq=4864), [(1, None), (8, 512)]),
    "olmo_hybrid": (dict(max_seq=6656, n_layer=8), [(2, None)]),
}
_PRESETS = {"laguna": "laguna-xs2", "solar_open2": "solar-open2",
            "phi4flash": "phi4-mini-flash",
            "olmo_hybrid": "olmo-hybrid-7b"}


def _cell_config(name):
    fam = family(name)
    over = dict(_CELLS[name][0])
    if name == "laguna":
        # the cell's five layers of the published forty
        over.update(layer_types=("full", "window", "window", "window",
                                 "full"),
                    heads_per_layer=(48, 64, 64, 64, 48),
                    mlp_types=("dense",) + ("sparse",) * 4)
    elif name == "solar_open2":
        over.update(n_layer=4, gqa_layers=(0,))
    return fam, fam.config(_PRESETS[name], **over)


@pytest.mark.parametrize("name", _CELLS)
def test_off_the_chip_a_family_counts_no_pairs(name):
    fam, cfg = _cell_config(name)
    assert fam.prefill_attention(cfg, 1024, 0, 1000) == (False, 0, 0)


@pytest.mark.parametrize("name", _CELLS)
def test_on_the_chip_a_family_counts_its_layers_walks(monkeypatch, name):
    """At the cell's published geometry every bucket takes the kernel,
    and the pairs are the layers' own: a full layer's triangle, a
    window layer's band."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fam, cfg = _cell_config(name)
    bq, bk = flash.BLOCK_Q, flash.BLOCK_K
    t_pad = 4096
    took, walked, square = fam.prefill_attention(cfg, t_pad, 0, t_pad)
    assert took
    nq, per = t_pad // bq, bq // bk
    triangle = per * nq * (nq + 1) // 2
    # a tile's first column reaches 511 keys back and its last is its
    # own diagonal: (512 + bq - 1) keys, cut off at slot 0
    band = sum(min(i * per, -(-511 // bk)) + per for i in range(nq))
    want = sum(n * (triangle if window is None else band)
               for n, window in _CELLS[name][1])
    assert walked == want
    assert square == sum(n for n, _ in _CELLS[name][1]) * nq * nq * per
    # a short tail behind a resident prefix walks the prefix's tiles
    # under a full layer and the ring's under a window layer
    took, walked, square = fam.prefill_attention(cfg, 1024, 3000, 24)
    assert took and 0 < walked <= square


@pytest.mark.parametrize("name", _CELLS)
def test_what_attended_a_prefill_lands_with_its_tokens(name):
    """Off the chip every paged prefill takes the `jnp` walk, and the
    engine's counter says so, one entry a prefill."""
    dep = build_llm_deployment(
        name, "nano", temperature=0.0, scheduler="continuous",
        kv_layout="paged", kv_block_size=16, prefill_bucket=16,
        max_slots=2, max_new_tokens=3)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 500, n).astype(np.int32) for n in (40, 21)]

    async def main():
        inst = dep.func_or_class()
        try:
            for p in prompts:
                await inst(p)
            return inst.engine_stats()
        finally:
            if hasattr(inst, "_engine_task"):
                inst.shutdown_engine()

    stats = asyncio.run(main())
    assert stats["prefill_attn"] == {
        "kernel": 0, "jnp": 2, "pairs_walked": 0, "pairs_square": 0,
        "walked_share": 0.0}
