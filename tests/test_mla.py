"""Latent attention and the paged latent pool (ray_tpu/models/kimi_k2.py,
kimi_k2_decode.py) against the plain reference
(benchmark/reference/kimi_k2.py), on the CPU at small sizes."""

import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode_common as dc
from ray_tpu.models import kimi_k2 as K
from ray_tpu.models import layers
from ray_tpu.models.kimi_k2_decode import (attend_blockwise,
                                           kimi_k2_decode_step,
                                           kimi_k2_generate,
                                           kimi_k2_init_paged_cache,
                                           kimi_k2_paged_prefill)
from ray_tpu.models.llama import apply_rope

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_ATOL = 1e-5
#: bf16 compute against the float32 reference at nano widths (logits of
#: std 0.16), four seeds: rms 0.8e-3 to 1.0e-3, a token's largest
#: error 2.5e-3 in the median; the largest of all 3.5e-3 to 2.0e-2: a
#: token whose 4th and 5th scores the bf16 stream swaps takes another
#: expert, and that is no rounding of the same sum
BF16_RMS, BF16_TOKEN_MEDIAN = 3e-3, 8e-3
_OVR = {"dtype": jnp.float32, "held": (0, 1, 2, 3, 4, 5)}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_kimi_{kind}", os.path.join(ROOT, "benchmark", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "kimi_k2")


@pytest.fixture(scope="module")
def tiny():
    cfg = K.kimi_k2_config("nano", **_OVR)
    return cfg, K.kimi_k2_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def wide_values():
    """Values WIDER than the keys' position-free part, in the published
    ratio of models/glm_dsa.py (192 + 64 against 256): 24 + 8 against
    32."""
    cfg = K.kimi_k2_config("nano", qk_nope_dim=24, v_head_dim=32, **_OVR)
    return cfg, K.kimi_k2_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(params=["nano", "values_wider_than_keys"])
def heads(request, tiny, wide_values):
    return tiny if request.param == "nano" else wide_values


def _stated(cfg):
    return dict(held=cfg.experts.held_ids, top_k=cfg.top_k,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                rope_theta=cfg.rope_theta, rope_factor=cfg.rope_factor,
                rope_orig_max=cfg.rope_orig_max, beta_fast=cfg.beta_fast,
                beta_slow=cfg.beta_slow, mscale=cfg.mscale,
                mscale_all_dim=cfg.mscale_all_dim, norm_topk=cfg.norm_topk,
                route_scale=cfg.route_scale, eps=cfg.rms_eps)


def _ref_logits(reference, params, cfg, tokens):
    return np.asarray(reference.logits(
        params, jnp.asarray(tokens), vocab_size=cfg.vocab_size,
        **_stated(cfg)))


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


@pytest.mark.parametrize("held", [None, (0, 1, 2, 3, 4, 5), (3, 9)])
def test_forward_matches_the_reference(reference, held):
    cfg = K.kimi_k2_config("nano", dtype=jnp.float32, held=held)
    params = K.kimi_k2_init(jax.random.PRNGKey(1), cfg)
    toks = _tokens(2, 2, 48)
    got = np.asarray(K.kimi_k2_forward(params, jnp.asarray(toks), cfg))
    np.testing.assert_allclose(got[..., :cfg.vocab_size],
                               _ref_logits(reference, params, cfg, toks),
                               atol=F32_ATOL)


def test_bf16_forward_stays_within_its_stated_tolerance(reference, tiny):
    cfg, params = tiny
    bf = K.kimi_k2_config("nano", held=cfg.held)
    toks = _tokens(3, 2, 48)
    got = np.asarray(K.kimi_k2_forward(params, jnp.asarray(toks), bf))
    err = np.abs(got[..., :cfg.vocab_size]
                 - _ref_logits(reference, params, cfg, toks))
    assert 1e-4 < np.sqrt(np.mean(err ** 2)) < BF16_RMS
    assert np.median(err.max(-1)) < BF16_TOKEN_MEDIAN


def test_loss_matches_the_reference(reference, tiny):
    cfg, params = tiny
    toks = _tokens(4, 2, 33)
    want = float(reference.loss(params, jnp.asarray(toks),
                                vocab_size=cfg.vocab_size, **_stated(cfg)))
    got = float(K.kimi_k2_loss(params, {"tokens": jnp.asarray(toks)}, cfg))
    assert abs(got - want) < 1e-5


def _attention_inputs(cfg, params, B=2, T=24):
    p = jax.tree.map(lambda a: a[0], params["moe"]["attn"])
    u = jax.random.normal(jax.random.PRNGKey(5), (B, T, cfg.d_model))
    cos, sin = K.rope_tables(jnp.arange(T)[None], cfg)
    return p, K.mla_project(u, p, cfg, cos, sin)


def test_absorbed_equals_expanded(heads):
    cfg, params = heads
    p, (q, ckv, kpe) = _attention_inputs(cfg, params)
    mask = jnp.tril(jnp.ones((24, 24), bool))[None]
    a = K.attend_expanded(q, ckv, kpe, p, mask, cfg)
    b = K.attend_absorbed(q, ckv, kpe, p, mask, cfg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)


def test_a_fresh_row_beside_the_view_equals_the_row_in_it(tiny):
    cfg, params = tiny
    p, (q, ckv, kpe) = _attention_inputs(cfg, params)
    last = q[:, -1:]
    inside = K.attend_absorbed(last, ckv, kpe, p,
                               jnp.ones((2, 1, 24), bool), cfg)
    beside = K.attend_absorbed(
        last, ckv, kpe, p, (jnp.arange(24) < 23)[None, None].repeat(2, 0),
        cfg, fresh=(ckv[:, -1:], kpe[:, -1:]))
    np.testing.assert_allclose(np.asarray(inside), np.asarray(beside),
                               atol=2e-6)


@pytest.mark.parametrize("pad,prefix", [(0, 0), (5, 0), (9, 32)])
def test_blockwise_equals_expanded(heads, pad, prefix):
    cfg, params = heads
    T, S = 64, cfg.max_seq
    p, (q, ckv, kpe) = _attention_inputs(cfg, params, B=1, T=S)
    col = jnp.arange(T)
    real = col >= pad
    logical = prefix + col - pad
    mask = (real[:, None]
            & (jnp.arange(S)[None, :] <= logical[:, None]))[None]
    want = K.attend_expanded(q[:, :T], ckv, kpe, p, mask, cfg)[0]
    got = attend_blockwise(q[0, :T], ckv[0], kpe[0], p, logical, real, cfg)
    assert got.shape == (T, cfg.n_head, cfg.v_head_dim)
    np.testing.assert_allclose(np.asarray(got[pad:]),
                               np.asarray(want[pad:]), atol=3e-6)
    assert float(jnp.abs(got[:pad]).max()) == 0.0 if pad else True
    # a second mask beside the causal one (an indexer's selection)
    pick = jax.random.bernoulli(jax.random.PRNGKey(pad), 0.4, (T, S)) \
        | jnp.eye(T, S, k=prefix - pad, dtype=bool)
    want = K.attend_expanded(q[:, :T], ckv, kpe, p, mask & pick[None],
                             cfg)[0]
    got = attend_blockwise(q[0, :T], ckv[0], kpe[0], p, logical, real, cfg,
                           selected=pick)
    np.testing.assert_allclose(np.asarray(got[pad:]),
                               np.asarray(want[pad:]), atol=3e-6)


def _paged_prefill(params, cfg, prompt, bucket, prefix_blocks=(), slot=1,
                   slots=3, cache=None, first_block=1):
    bs = 16
    n = len(prompt)
    if cache is None:
        cache = kimi_k2_init_paged_cache(cfg, slots, num_blocks=40,
                                         block_size=bs)
    prefix_len = len(prefix_blocks) * bs
    n_tail = n - prefix_len
    t_pad = -(-n_tail // bucket) * bucket
    toks = np.zeros((1, t_pad), np.int32)
    toks[0, t_pad - n_tail:] = prompt[prefix_len:]
    row_bt = np.zeros((cfg.max_seq // bs,), np.int32)
    need = -(-n // bs) + 1
    own = list(prefix_blocks) + list(range(
        first_block, first_block + need - len(prefix_blocks)))
    row_bt[:len(own)] = own
    logits, cache = jax.jit(
        lambda c: kimi_k2_paged_prefill(
            params, c, jnp.asarray(toks), cfg, row_bt=jnp.asarray(row_bt),
            prefix_len=prefix_len, n_tail=n_tail, slot=slot))(cache)
    return logits, cache, own


@pytest.mark.parametrize("bucket", [16, 64])
def test_paged_prefill_then_decode_is_the_full_forward(reference, tiny,
                                                       bucket):
    cfg, params = tiny
    toks = _tokens(6, 1, 45)[0]
    want = _ref_logits(reference, params, cfg, toks[None])[0]
    logits, cache, _ = _paged_prefill(params, cfg, toks[:40], bucket)
    np.testing.assert_allclose(np.asarray(logits)[:512], want[39],
                               atol=F32_ATOL)
    step = jax.jit(lambda c, t: kimi_k2_decode_step(params, c, t, cfg))
    for i in range(40, 45):
        feed = np.zeros((3,), np.int32)
        feed[1] = toks[i]
        lg, cache = step(cache, jnp.asarray(feed))
        np.testing.assert_allclose(np.asarray(lg)[1, :512], want[i],
                                   atol=F32_ATOL)
    assert int(cache["pos"][1]) == 45 and int(cache["pos"][0]) == 0


def test_a_prefix_hit_on_latent_blocks_leaves_the_answer_unchanged(tiny):
    cfg, params = tiny
    shared = _tokens(7, 1, 32)[0]
    first = np.concatenate([shared, _tokens(8, 1, 9)[0]])
    second = np.concatenate([shared, _tokens(9, 1, 14)[0]])
    _, cache, own = _paged_prefill(params, cfg, first, 16, slot=0)
    cold, _, _ = _paged_prefill(params, cfg, second, 16, slot=1)
    hit, cache, _ = _paged_prefill(params, cfg, second, 16,
                                   prefix_blocks=own[:2], slot=1,
                                   cache=cache, first_block=10)
    np.testing.assert_allclose(np.asarray(hit), np.asarray(cold),
                               atol=F32_ATOL)
    assert dc.block_bytes(cache) == 16 * cfg.n_layer * cfg.latent_dim * 4


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_generate_is_greedy_under_the_reference(reference, tiny, layout):
    cfg, params = tiny
    prompt = _tokens(10, 2, 20)
    out = np.asarray(kimi_k2_generate(
        params, jnp.asarray(prompt), cfg, max_new_tokens=6,
        temperature=0.0, kv_layout=layout))
    lg = _ref_logits(reference, params, cfg, out[:, :-1])
    assert np.array_equal(lg[:, 19:].argmax(-1), out[:, 20:])


def test_ragged_rows_decode_as_they_would_alone(tiny):
    cfg, params = tiny
    a, b = _tokens(11, 1, 20)[0], _tokens(12, 1, 13)[0]
    batch = np.zeros((2, 20), np.int32)
    batch[0], batch[1, 7:] = a, b
    both = np.asarray(kimi_k2_generate(
        params, jnp.asarray(batch), cfg, max_new_tokens=5, temperature=0.0,
        lengths=jnp.asarray([20, 13])))
    alone = np.asarray(kimi_k2_generate(
        params, jnp.asarray(b[None]), cfg, max_new_tokens=5,
        temperature=0.0))
    assert np.array_equal(both[1, 20:], alone[0, 13:])


def test_the_yarn_table_and_scale_are_the_published_models():
    cfg = K.kimi_k2_config("kimi-k2-code")
    assert layers.yarn_correction_range(cfg) == (8, 20)
    inv = layers.yarn_inv_freq(cfg)
    f = 50000.0 ** (-np.arange(32) * 2 / 64)
    np.testing.assert_allclose(inv[:9], f[:9], rtol=1e-6)      # kept
    np.testing.assert_allclose(inv[20:], f[20:] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv[14], f[14] / 64 * 0.5 + f[14] * 0.5,
                               rtol=1e-6)                      # mid-ramp
    assert abs(K.softmax_scale(cfg) - 0.14468) < 5e-6
    assert abs(K.softmax_scale(cfg) - 192 ** -0.5
               * (0.1 * math.log(64) + 1) ** 2) < 1e-12
    cos, sin = K.rope_tables(jnp.asarray([[0, 3]]), cfg)
    np.testing.assert_allclose(np.asarray(cos[0, 1]), np.cos(3 * inv),
                               atol=1e-6)


def test_rotate_pairs_as_llamas_rope_does():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 8))
    ang = jnp.arange(6, dtype=jnp.float32)[:, None] * jnp.asarray(
        [1.0, 0.5, 0.25, 0.125])
    want = apply_rope(x, jnp.cos(ang), jnp.sin(ang))
    got = K.rotate(x, jnp.cos(ang)[None, :, None],
                   jnp.sin(ang)[None, :, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_init_counts_what_param_count_says(tiny):
    cfg, params = tiny
    n = sum(a.size for a in jax.tree.leaves(params))
    pad = 2 * (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model
    assert n - pad == K.kimi_k2_param_count(cfg)
    axes = K.kimi_k2_logical_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes,
                     is_leaf=lambda a: isinstance(a, tuple)))


def test_the_published_preset_is_the_published_model():
    cfg = K.kimi_k2_config("kimi-k2-code")
    assert 1.02e12 < K.kimi_k2_param_count(cfg) < 1.04e12
    cut = K.kimi_k2_config("kimi-k2-code", n_layer=6, held=range(12),
                           vocab_size=20480)
    assert K.kimi_k2_param_count(cut) == 4_173_177_728
    assert cut.latent_dim * 2 == 1152


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_programs_update_the_latent_pool_in_place(tiny, name):
    """With the cache donated, the pools come back as the buffers they
    went in as: no second pool exists."""
    cfg, params = tiny
    cache = kimi_k2_init_paged_cache(cfg, 3, num_blocks=40, block_size=16)
    if name == "decode":
        fn = jax.jit(lambda c: kimi_k2_decode_step(
            params, c, jnp.zeros((3,), jnp.int32), cfg)[1],
            donate_argnums=(0,))
    else:
        fn = jax.jit(lambda c: kimi_k2_paged_prefill(
            params, c, jnp.zeros((1, 16), jnp.int32), cfg,
            row_bt=jnp.arange(8, dtype=jnp.int32), prefix_len=0,
            n_tail=16, slot=0)[1], donate_argnums=(0,))
    before = {k: cache[k].unsafe_buffer_pointer() for k in ("ckv", "kpe")}
    out = fn(cache)
    assert {k: out[k].unsafe_buffer_pointer()
            for k in before} == before
    assert cache["ckv"].is_deleted()


def test_a_mesh_is_refused_for_the_latent_pool(tiny):
    cfg, _ = tiny
    with pytest.raises(ValueError, match="latent pool"):
        kimi_k2_init_paged_cache(cfg, 2, num_blocks=20, block_size=16,
                                 mesh=object())
