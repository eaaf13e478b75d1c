"""ops/mla_flash_prefill.py: the prefill's flash kernel, in the Pallas
interpreter, held to the `jnp` walk it replaces on the chip
(models/kimi_k2_decode.attend_blockwise) and to the dense masked
attention (models/kimi_k2.attend_expanded); and the choice between the
two paths, read from the engine's counter.

Toy widths that keep the published ratios (nope 16 != rope 8 != v 16
... 4 heads); every case of a dtype shares ONE traced kernel: the tail's
place (`prefix_len`, `pad`) is data.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.kimi_k2 import (attend_expanded, expand_latents,
                                    kimi_k2_config, softmax_scale)
from ray_tpu.models.kimi_k2_decode import (attend_blockwise,
                                           kimi_k2_prefill_attention)
from ray_tpu.ops import mla_flash_prefill as flash

T, S, TILE, STRIP = 64, 128, 32, 16

#: name -> (prefix_len, pad): where the T-column tail stands
PLACES = {
    "pad_columns_no_prefix": (0, 13),
    "short_tail_behind_a_prefix": (60, 50),     # the repeat_hit shape
    "ends_inside_a_key_tile": (40, 7),
    "a_whole_tile_of_pads": (0, 40),
    "the_view_filled": (64, 0),
    "no_pad_no_prefix": (0, 0),
}


def _problem(dtype, **dims):
    cfg = kimi_k2_config("nano", dtype=dtype, max_seq=S, **dims)
    H, c = cfg.n_head, cfg.kv_lora_rank
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    p = {"wk_b": jax.random.normal(ks[0], (c, H, cfg.qk_nope_dim)) * 0.3,
         "wv_b": jax.random.normal(ks[1], (c, H, cfg.v_head_dim)) * 0.3}
    q = jax.random.normal(ks[2], (T, H, cfg.qk_head_dim)).astype(dtype)
    ckv = jax.random.normal(ks[3], (S, c)).astype(dtype)
    kpe = jax.random.normal(ks[4], (S, cfg.qk_rope_dim)).astype(dtype)
    k_nope, v = expand_latents(ckv, p, cfg)

    def kernel(prefix_len, pad):
        return flash.mla_flash_prefill(
            q, k_nope, kpe, v, prefix_len, pad, scale=softmax_scale(cfg),
            block_q=TILE, block_k=TILE, strip=STRIP, interpret=True)

    return cfg, p, q, ckv, kpe, kernel


@pytest.fixture(scope="module", params=[jnp.float32, jnp.bfloat16],
                ids=["f32", "bf16"])
def problem(request):
    return _problem(request.param)


def test_values_wider_than_the_keys_free_part(monkeypatch):
    """models/glm_dsa.py's head shape, 192 + 64 against 256, in
    miniature (24 + 8 against 32): keys and values of one width, the
    keys' position-free part narrower than the values."""
    cfg, p, q, ckv, kpe, kernel = _problem(jnp.float32, qk_nope_dim=24,
                                           v_head_dim=32)
    prefix_len, pad = PLACES["short_tail_behind_a_prefix"]
    col = jnp.arange(T)
    out = kernel(prefix_len, pad)
    assert out.shape == (T, cfg.n_head, 32) and cfg.qk_head_dim == 32
    walk = attend_blockwise(q, ckv, kpe, p, prefix_len + col - pad,
                            col >= pad, cfg)
    np.testing.assert_allclose(out, walk, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("place", PLACES)
def test_the_kernel_is_the_jnp_walk(problem, place):
    cfg, p, q, ckv, kpe, kernel = problem
    prefix_len, pad = PLACES[place]
    tol = 1e-5 if cfg.dtype == jnp.float32 else 5e-2
    col = jnp.arange(T)
    logical, real = prefix_len + col - pad, col >= pad
    out = kernel(prefix_len, pad)
    assert out.shape == (T, cfg.n_head, cfg.v_head_dim)
    assert out.dtype == cfg.dtype
    walk = attend_blockwise(q, ckv, kpe, p, logical, real, cfg)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               walk.astype(jnp.float32), atol=tol, rtol=tol)
    # a pad column sees no key and returns zeros, exactly
    assert not np.asarray(out[:pad].astype(jnp.float32)).any()
    # the dense attention under the equivalent mask (whose pad rows are
    # a softmax over nothing: not compared)
    mask = (jnp.arange(S)[None, :] <= logical[:, None]) & real[:, None]
    dense = attend_expanded(q[None], ckv[None], kpe[None], p, mask[None],
                            cfg)[0]
    np.testing.assert_allclose(out[pad:].astype(jnp.float32),
                               dense[pad:].astype(jnp.float32), atol=tol,
                               rtol=tol)


@pytest.mark.parametrize("prefix_len,pad,visits", [
    (0, 0, [1, 2]), (0, 13, [1, 2]), (60, 50, [1, 3]), (40, 7, [3, 4]),
    (0, 40, [1, 1]), (64, 0, [3, 4]), (1000, 0, [3, 4]),
    (90, 40, [1, 4])])
def test_the_walk_stops_at_the_diagonal(prefix_len, pad, visits):
    """Key tiles a query tile visits: as far as its last column reaches,
    one for a tile of pads, never past what the shape has room for."""
    got = flash.walk(T, S, prefix_len, pad, TILE, TILE)
    assert got.tolist() == visits
    traced = jax.jit(lambda a, b: flash.walk(T, S, a, b, TILE, TILE,
                                             xp=jnp))(prefix_len, pad)
    assert traced.tolist() == visits


def test_shapes_no_tile_divides_are_refused():
    assert flash.fits(1024, 8704) and flash.fits(8192, 8704)
    assert not flash.fits(1000, 8704) and not flash.fits(1024, 8700)
    assert not flash.fits(0, 8704)
    x = jnp.zeros((48, 2, 24))
    with pytest.raises(ValueError, match="whole tiles"):
        flash.mla_flash_prefill(x, x[..., :16], x[:, 0, :8], x[..., :16],
                                0, 0, scale=1.0, block_q=TILE,
                                block_k=TILE, strip=STRIP, interpret=True)


@pytest.mark.parametrize("backend,t_pad,kernel", [
    ("cpu", 1024, False), ("tpu", 1024, True), ("tpu", 8192, True),
    ("tpu", 1000, False), ("tpu", 40, False)])
def test_the_path_is_picked_from_backend_and_shape(monkeypatch, backend,
                                                   t_pad, kernel):
    """What `kimi_k2_paged_prefill` asks before it attends, and what the
    engine counts from: the chip and a tail the tiles divide take the
    kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg = kimi_k2_config("nano", max_seq=8704)
    took, walked, square = kimi_k2_prefill_attention(cfg, t_pad, 0,
                                                     t_pad - 3)
    assert took is kernel
    if not kernel:
        assert (walked, square) == (0, 0)
        return
    # query tiles of BLOCK_Q over key tiles half as long: tile i walks
    # 2 (i + 1) of the 2 n the sequence holds
    n, per = t_pad // flash.BLOCK_Q, flash.BLOCK_Q // flash.BLOCK_K
    assert walked == per * n * (n + 1) // 2 and square == per * n * n
    # a short tail behind a resident prefix walks the prefix's tiles too
    took, walked, square = kimi_k2_prefill_attention(cfg, 1024, 7000, 24)
    assert took and (walked, square) == (14, 14)
    # ... and a tile of pads before the tail's visits one
    took, walked, square = kimi_k2_prefill_attention(cfg, 2048, 7000, 24)
    assert took and (walked, square) == (1 + 14, 2 * 14)
