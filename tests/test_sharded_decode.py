"""Mesh-sharded serving parity: tensor-parallel paged decode over an
8-device fake mesh must be BIT-IDENTICAL (greedy token streams) to the
single-chip dense oracle for both families.

Sharding is driven entirely by committed input shardings: params and
the KV pool are device_put under parallel.sharding.DECODE_RULES (heads
/ mlp / vocab / pool KV-heads over `tensor`; everything the host
scheduler reads stays replicated) and GSPMD propagates them through
the UNCHANGED jitted programs.  Logits are not asserted bitwise —
row-parallel contractions all-reduce partial sums in a different
order than a single chip — but greedy argmax token streams are, and
that is the property serving correctness rests on.

conftest.py forces 8 virtual CPU devices, so every test here runs in
tier-1.
"""

import asyncio
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import (gpt2_config, gpt2_init, gpt2_logical_axes,
                            llama_config, llama_init,
                            llama_logical_axes)  # noqa: E402
from ray_tpu.models import gpt2_decode, llama_decode  # noqa: E402
from ray_tpu.models.decode_common import (cache_logical_axes,
                                          make_vocab_tail_mask,
                                          sample_token)  # noqa: E402
from ray_tpu.parallel import (MeshSpec, fake_mesh,
                              mesh_axes_for_shape)  # noqa: E402
from ray_tpu.parallel.sharding import (DECODE_RULES,
                                       shard_by_shape)  # noqa: E402

BS = 16
_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them in CI)")
    return fake_mesh(8, MeshSpec(data=4, tensor=2))


def _family(name):
    """(cfg, params, axes, prefill, paged_prefill, decode_step,
    init_paged_cache, generate) — params NOT yet sharded."""
    if name == "gpt2":
        cfg = gpt2_config("nano", **_OVR)
        return (cfg, gpt2_init(jax.random.PRNGKey(0), cfg),
                gpt2_logical_axes(cfg), gpt2_decode.prefill,
                gpt2_decode.paged_prefill, gpt2_decode.decode_step,
                gpt2_decode.init_paged_cache, gpt2_decode.generate)
    cfg = llama_config("nano", **_OVR)
    return (cfg, llama_init(jax.random.PRNGKey(0), cfg),
            llama_logical_axes(cfg), llama_decode.llama_prefill,
            llama_decode.llama_paged_prefill,
            llama_decode.llama_decode_step,
            llama_decode.llama_init_paged_cache,
            llama_decode.llama_generate)


def _right_aligned(tokens, t_pad):
    out = np.zeros((1, t_pad), np.int32)
    out[0, t_pad - len(tokens):] = tokens
    return jnp.asarray(out)


@functools.lru_cache(maxsize=None)
def _jitted(family):
    """Module-lifetime jitted (decode_step, paged_prefill) per family:
    the sharded programs compile once and every test reuses the XLA
    cache — eager dispatch of sharded nano ops over 8 devices is what
    dominates otherwise."""
    _, _, _, _, paged_prefill, decode_step, _, _ = _family(family)
    return (jax.jit(decode_step, static_argnums=3),
            jax.jit(paged_prefill, static_argnums=3))


# ---------------------------------------------------------------------------
# sharding structure
# ---------------------------------------------------------------------------

def test_divisibility_guard_replicates_non_dividing_dims(mesh):
    # 2 heads / tensor=2 shards; 1 KV head / tensor=2 replicates;
    # odd dims replicate regardless of the rule table
    spec = mesh_axes_for_shape((4, 2, 32), (None, "heads", None), mesh,
                               DECODE_RULES)
    assert tuple(spec) == (None, "tensor")
    spec = mesh_axes_for_shape((4, 1, 32), (None, "kv_heads", None),
                               mesh, DECODE_RULES)
    assert tuple(spec) == ()
    spec = mesh_axes_for_shape((3,), ("mlp",), mesh, DECODE_RULES)
    assert tuple(spec) == ()


def test_params_and_pool_committed_to_mesh(mesh):
    cfg, params, axes, *_, init_paged, _ = _family("gpt2")
    sp = shard_by_shape(params, axes, mesh, DECODE_RULES)
    qkv = sp["blocks"]["attn"]["qkv_w"]
    assert "tensor" in tuple(qkv.sharding.spec)
    # per-chip shard halves the heads dim
    full = qkv.shape
    shard = qkv.sharding.shard_shape(full)
    assert shard[-2] * 2 == full[-2]

    cache = init_paged(cfg, 2, num_blocks=17, block_size=BS, mesh=mesh)
    kspec = tuple(cache["k"].sharding.spec)
    assert kspec == (None, None, None, "tensor")
    # host-facing leaves stay replicated
    for name in ("block_tables", "pos", "start"):
        assert tuple(cache[name].sharding.spec) in ((), (None,),
                                                    (None, None))
    # the paged axes annotation covers every leaf
    assert set(cache_logical_axes(cache)) == set(cache)


def test_llama_gqa_pool_replicates_but_q_heads_shard(mesh):
    cfg, params, axes, *_, init_paged, _ = _family("llama")
    sp = shard_by_shape(params, axes, mesh, DECODE_RULES)
    # wq: 2 query heads shard over tensor=2
    wq = sp["blocks"]["attn"]["wq"]
    assert "tensor" in tuple(wq.sharding.spec)
    # wk: 1 KV head — the guard replicates instead of erroring
    wk = sp["blocks"]["attn"]["wk"]
    assert "tensor" not in tuple(s for s in wk.sharding.spec
                                 if isinstance(s, str))
    cache = init_paged(cfg, 2, num_blocks=17, block_size=BS, mesh=mesh)
    assert "tensor" not in tuple(s for s in cache["k"].sharding.spec
                                 if isinstance(s, str))


# ---------------------------------------------------------------------------
# model-layer parity: sharded paged decode == single-chip dense oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_sharded_paged_decode_matches_dense_oracle(family, mesh):
    cfg, params, axes, prefill, _, _, init_paged, generate = \
        _family(family)
    decode_step, paged_prefill = _jitted(family)
    sp = shard_by_shape(params, axes, mesh, DECODE_RULES)

    rng = np.random.RandomState(3)
    prompt = rng.randint(2, cfg.vocab_size, 9).astype(np.int32)
    new = 6
    oracle = np.asarray(generate(params, jnp.asarray(prompt[None]),
                                 cfg, max_new_tokens=new,
                                 temperature=0.0))[0, len(prompt):]

    nb_row = cfg.max_seq // BS
    cache = init_paged(cfg, 2, num_blocks=1 + 2 * nb_row,
                       block_size=BS, mesh=mesh)
    row_bt = np.zeros(nb_row, np.int32)
    row_bt[0] = 1
    logits, cache = paged_prefill(
        sp, cache, _right_aligned(prompt, 16), cfg,
        row_bt=jnp.asarray(row_bt), prefix_len=np.int32(0),
        n_tail=np.int32(len(prompt)), slot=np.int32(0))
    tail = make_vocab_tail_mask(cfg)
    tok = sample_token(logits[None], None, 0.0, tail)
    cur = jnp.asarray([int(tok[0]), 0], jnp.int32)  # row 1 idle
    stream = [int(tok[0])]
    for _ in range(new - 1):
        logits, cache = decode_step(sp, cache, cur, cfg)
        nxt = sample_token(logits, None, 0.0, tail)
        stream.append(int(nxt[0]))
        cur = jnp.asarray([int(nxt[0]), int(nxt[1])], jnp.int32)
    assert stream == oracle.tolist()


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_sharded_prefix_reuse_prefill_matches_dense(family, mesh):
    """Prefix-reuse under the mesh: sequence B extends blocks written
    by sequence A's sharded prefill; its logits must match dense
    full-prompt prefill (numerically — the all-reduce changes float
    summation order) and its greedy stream must match exactly."""
    cfg, params, axes, prefill, _, _, init_paged, generate = \
        _family(family)
    decode_step, paged_prefill = _jitted(family)
    sp = shard_by_shape(params, axes, mesh, DECODE_RULES)
    rng = np.random.RandomState(7)
    shared = rng.randint(2, cfg.vocab_size, 32).astype(np.int32)
    # equal lengths: the dense generate oracle compiles ONE shape
    a = np.concatenate([shared, rng.randint(2, cfg.vocab_size, 3)
                        .astype(np.int32)])
    b = np.concatenate([shared, rng.randint(2, cfg.vocab_size, 3)
                        .astype(np.int32)])

    nb_row = cfg.max_seq // BS
    cache = init_paged(cfg, 2, num_blocks=1 + 2 * nb_row,
                       block_size=BS, mesh=mesh)
    bt_a = jnp.arange(1, 1 + nb_row, dtype=jnp.int32)
    _, cache = paged_prefill(sp, cache, _right_aligned(a, 48), cfg,
                             row_bt=bt_a, prefix_len=np.int32(0),
                             n_tail=np.int32(len(a)), slot=np.int32(0))
    bt_b = np.zeros(nb_row, np.int32)
    bt_b[0], bt_b[1], bt_b[2] = 1, 2, 1 + nb_row
    got, cache = paged_prefill(sp, cache, _right_aligned(b[32:], 16),
                               cfg, row_bt=jnp.asarray(bt_b),
                               prefix_len=np.int32(32),
                               n_tail=np.int32(len(b) - 32),
                               slot=np.int32(1))
    want, _ = prefill(params, jnp.asarray(b[None]), cfg,
                      lengths=jnp.asarray([len(b)]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want)[0],
                               atol=1e-4)

    # greedy streams from the shared sharded pool == dense solo
    # (equal lengths -> one batched oracle generate call)
    new = 4
    out = np.asarray(generate(params, jnp.asarray(np.stack([a, b])),
                              cfg, max_new_tokens=new, temperature=0.0))
    oracle = {0: out[0, len(a):], 1: out[1, len(b):]}
    tail = make_vocab_tail_mask(cfg)
    tok = jnp.asarray([int(oracle[0][0]),
                       int(np.argmax(np.asarray(got)))], jnp.int32)
    streams = [[], []]
    for _ in range(new):
        streams[0].append(int(tok[0]))
        streams[1].append(int(tok[1]))
        logits, cache = decode_step(sp, cache, tok, cfg)
        tok = sample_token(logits, None, 0.0, tail)
    assert streams[0] == oracle[0].tolist()
    assert streams[1] == oracle[1].tolist()


# ---------------------------------------------------------------------------
# continuous-scheduler e2e under the mesh
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_continuous_engine_two_waves_under_mesh(family, mesh):
    """6 requests through 3 slots (two admission waves) on the sharded
    engine: every caller gets the bit-identical dense-solo greedy
    continuation, and engine_stats reports the live mesh."""
    from ray_tpu.serve.llm import build_llm_deployment

    max_new = 5
    rng = np.random.RandomState(21)
    # two distinct lengths (-> 2 prefill buckets, 2 oracle compile
    # shapes) keeps the two-wave coverage without compiling a dense
    # generate program per request
    prompts = [rng.randint(2, 500, n).astype(np.int32)
               for n in (9, 23, 9, 23, 9, 23)]
    dep = build_llm_deployment(
        family, "nano", max_new_tokens=max_new, temperature=0.0,
        scheduler="continuous", kv_layout="paged", kv_block_size=BS,
        prefill_bucket=16, max_slots=3, mesh=mesh,
        config_overrides=_OVR)

    async def main():
        inst = dep.func_or_class()
        try:
            outs = await asyncio.wait_for(
                asyncio.gather(*[inst(p) for p in prompts]), 300)
            stats = inst.engine_stats()
        finally:
            inst.shutdown_engine()
        return outs, stats

    outs, stats = asyncio.run(main())
    cfg, params, *_, generate = _family(family)
    for n in (9, 23):  # one batched oracle generate per length
        idx = [i for i, p in enumerate(prompts) if len(p) == n]
        want = np.asarray(generate(
            params, jnp.asarray(np.stack([prompts[i] for i in idx])),
            cfg, max_new_tokens=max_new, temperature=0.0))
        for row, i in enumerate(idx):
            np.testing.assert_array_equal(outs[i], want[row])
    assert stats["requests"]["finished"] == 6
    assert stats["mesh"]["axes"] == {"data": 4, "tensor": 2}
    assert stats["mesh"]["n_devices"] == 8
    assert stats["mesh"]["kv_shards"] == (2 if family == "gpt2" else 1)
    kv = stats["kv_cache"]
    assert kv["pool_bytes_per_chip"] * kv["tensor_shards"] \
        == kv["pool_bytes"]


def test_jit_cache_keyed_by_layout_and_mesh(mesh):
    """Regression (round-9 satellite): equal-config engines differing
    only in kv_layout or mesh must NOT share jitted programs."""
    from ray_tpu.models.families import family
    from ray_tpu.serve.llm import _jitted_engine_fns

    gpt2 = family("gpt2")
    cfg = gpt2_config("nano", **_OVR)
    base = _jitted_engine_fns(gpt2, cfg, 0.0, kv_layout="dense",
                              mesh=None)
    paged = _jitted_engine_fns(gpt2, cfg, 0.0, kv_layout="paged",
                               mesh=None)
    meshed = _jitted_engine_fns(gpt2, cfg, 0.0, kv_layout="paged",
                                mesh=mesh)
    assert base is not paged
    assert paged is not meshed
    # same identity -> same cached tuple (the cache still works)
    again = _jitted_engine_fns(family("gpt2"), cfg, 0.0,
                               kv_layout="paged", mesh=mesh)
    assert again is meshed
