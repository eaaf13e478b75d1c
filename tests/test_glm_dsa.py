"""Latent attention over what a learned indexer selects
(ray_tpu/models/glm_dsa.py, glm_dsa_decode.py) against the plain
reference (benchmark/reference/glm_dsa.py), on the CPU at ``nano``:
the full forward and the selected sets, the dense and the paged cache,
a prompt in chunks, a prefix hit over shared ``kidx`` blocks, contexts
the selection does not bite, and the family through the serving
engine."""

import asyncio
import collections
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import scopes
from ray_tpu.models import decode_common as dc
from ray_tpu.models import experts
from ray_tpu.models import families
from ray_tpu.models import glm_dsa as G
from ray_tpu.models import kimi_k2 as K
from ray_tpu.models import kimi_k2_decode
from ray_tpu.models.glm_dsa_decode import (glm_dsa_decode_step,
                                           glm_dsa_generate,
                                           glm_dsa_init_paged_cache,
                                           glm_dsa_paged_prefill,
                                           glm_dsa_prefill)
from ray_tpu.ops import dsa
from ray_tpu.ops.mla_paged_decode import mla_paged_decode
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: float32 program against the float32 reference: the same sums in
#: another order (logits of deviation 0.1)
F32_ATOL = 1e-5
#: how far from the reference's LAST selected score a position may
#: stand, by the reference's own score, and be selected by one side
#: alone.  Float32 against float32 the scores differ by rounding of the
#: same sums (1e-7 of scores of deviation 0.6); the bf16 program's
#: operands put the furthest such position 0.006 to 0.011 from the last
#: place at these widths (three seeds, 18 to 38 places of 2 x 96 rows):
#: 0.03 is three times the largest seen and a twentieth of the scores'
#: deviation
BAND_F32, BAND_BF16 = 1e-5, 3e-2
_OVR = {"dtype": jnp.float32}
T = 96          # prompts longer than nano's index_topk of 24


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_glm_{kind}", os.path.join(ROOT, "benchmark", kind, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "glm_dsa")


@pytest.fixture(scope="module")
def tiny():
    cfg = G.glm_dsa_config("nano", **_OVR)
    assert cfg.v_head_dim != cfg.qk_nope_dim
    assert cfg.attn_block < cfg.index_topk < T
    assert 0 < len(cfg.held) < cfg.n_routed
    return cfg, G.glm_dsa_init(jax.random.PRNGKey(0), cfg)


def _stated(cfg):
    return dict(held=cfg.experts.held_ids, top_k=cfg.top_k,
                qk_nope_dim=cfg.qk_nope_dim, qk_rope_dim=cfg.qk_rope_dim,
                rope_theta=cfg.rope_theta, index_topk=cfg.index_topk,
                index_eps=cfg.index_eps, norm_topk=cfg.norm_topk,
                route_scale=cfg.route_scale, eps=cfg.rms_eps)


def _ref_logits(reference, params, cfg, tokens):
    return np.asarray(reference.logits(
        params, jnp.asarray(tokens), vocab_size=cfg.vocab_size,
        **_stated(cfg)))


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


def _forward(cfg, params, tokens):
    return np.asarray(jax.jit(lambda t: G.glm_dsa_forward(params, t, cfg))(
        jnp.asarray(tokens)))[..., :cfg.vocab_size]


# -- the selection, then the logits -------------------------------------------

def _layer0_selection(reference, cfg, params, tokens, dtype):
    """Layer 0's selection by the program computing in `dtype`, and the
    reference's scores and selection from the same float32 input."""
    run = G.glm_dsa_config("nano", dtype=dtype)
    n = tokens.shape[1]
    p = jax.tree.map(lambda a: a[0], params["dense"])
    x = K.embed(params, jnp.asarray(tokens), run)
    u = K.rmsnorm(x, p["ln1"]["scale"], run.rms_eps)
    cos, sin = K.rope_tables(jnp.arange(n)[None], run)
    *_, cq = K.mla_project(u, p["attn"], run, cos, sin, latent=True)
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((n, n), bool)),
                              (tokens.shape[0], n, n))
    got = G.selection(*G.index_project(u, cq, p["indexer"], run, cos, sin),
                      causal, run)
    x32 = params["wte"][jnp.asarray(tokens)].astype(jnp.float32)
    u32 = reference._rmsnorm(x32, p["ln1"]["scale"], cfg.rms_eps)
    cq32 = reference._rmsnorm(reference._mm(u32, p["attn"]["wq_a"]),
                              p["attn"]["q_norm"], cfg.rms_eps)
    parts = reference._index_parts(u32, cq32, p["indexer"], cos[0], sin[0],
                                   cfg.qk_rope_dim, cfg.index_eps)
    scores = np.asarray(reference._index_scores(parts[0], parts[1],
                                                parts[2]))
    want = np.asarray(reference._selected(
        u32, cq32, p["indexer"], cos[0], sin[0], cfg.qk_rope_dim,
        cfg.index_topk, cfg.index_eps))
    return np.asarray(got), want, scores


@pytest.mark.parametrize("dtype,band", [(jnp.float32, BAND_F32),
                                        (jnp.bfloat16, BAND_BF16)],
                         ids=["f32", "bf16"])
def test_the_selected_sets_differ_only_at_the_last_place(reference, tiny,
                                                         dtype, band):
    """Both sides select ``min(topk, t + 1)`` positions a query, and
    where the sets differ the reference's own score of the position
    lies within `band` of its last selected score."""
    cfg, params = tiny
    got, want, scores = _layer0_selection(reference, cfg, params,
                                          _tokens(1, 2, T), dtype)
    counts = np.minimum(np.arange(T) + 1, cfg.index_topk)
    assert (got.sum(-1) == counts).all() and (want.sum(-1) == counts).all()
    last = np.where(want, scores, np.inf).min(-1)            # (B, T)
    off = np.abs(scores - last[..., None])[got != want]
    assert off.size == 0 or off.max() <= band, off.max()
    if dtype == jnp.float32:
        assert off.size == 0
    else:
        # the band is no licence: five in six rows agree place for place
        assert (got != want).any(-1).mean() < 1 / 6


def test_forward_matches_the_reference_at_every_position(reference, tiny):
    cfg, params = tiny
    toks = _tokens(2, 2, T)
    np.testing.assert_allclose(_forward(cfg, params, toks),
                               _ref_logits(reference, params, cfg, toks),
                               atol=F32_ATOL)


def test_loss_matches_the_reference(reference, tiny):
    cfg, params = tiny
    toks = _tokens(3, 2, 65)
    want = float(reference.loss(params, jnp.asarray(toks),
                                vocab_size=cfg.vocab_size, **_stated(cfg)))
    got = float(G.glm_dsa_loss(params, {"tokens": jnp.asarray(toks)}, cfg))
    assert abs(got - want) < 1e-5


def test_the_selection_bites_and_a_short_context_is_dense_mla(tiny):
    """Up to ``index_topk`` positions everything is selected and the
    layer is kimi_k2's dense latent attention over the same weights;
    past it the two part."""
    cfg, params = tiny
    toks = _tokens(4, 1, T)
    dense = np.asarray(jax.jit(lambda t: K.kimi_k2_forward(params, t, cfg))(
        jnp.asarray(toks)))[..., :cfg.vocab_size]
    got = _forward(cfg, params, toks)
    k = cfg.index_topk
    np.testing.assert_allclose(got[:, :k], dense[:, :k], atol=2e-6)
    assert np.abs(got[:, k:] - dense[:, k:]).max() > 1e-2


def test_the_parameter_count_is_the_trees(tiny):
    cfg, params = tiny
    leaves = sum(a.size for a in jax.tree.leaves(params))
    assert G.glm_dsa_param_count(cfg) == leaves \
        - 2 * (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model
    axes = G.glm_dsa_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


# -- the caches ---------------------------------------------------------------

@pytest.fixture(scope="module")
def programs(tiny):
    cfg, params = tiny
    return (
        jax.jit(lambda c, t: glm_dsa_decode_step(params, c, t, cfg)),
        jax.jit(lambda c, t, bt, pl, nt, s: glm_dsa_paged_prefill(
            params, c, t, cfg, row_bt=bt, prefix_len=pl, n_tail=nt,
            slot=s)))


@pytest.fixture(params=["jnp", "kernel"])
def path(request, monkeypatch):
    """The paged decode step by its ``jnp`` path (the CPU's), and once
    more as the chip takes it: the backend test says "tpu" and the walk
    under the selection's mask (ops/mla_paged_decode.py) runs in the
    Pallas interpreter, the experts' kernels too."""
    walked = []

    def walk(*args, selected, **kw):
        walked.append(selected.shape)
        return mla_paged_decode(*args, selected=selected, interpret=True,
                                **kw)

    if request.param == "kernel":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(kimi_k2_decode, "mla_paged_decode", walk)
        for kernel in ("moe_dispatch", "moe_combine", "_fused"):
            monkeypatch.setattr(experts, kernel, functools.partial(
                getattr(experts, kernel), interpret=True))
    yield request.param
    assert bool(walked) == (request.param == "kernel")


def test_prefill_then_decode_is_the_full_forward_dense_and_paged(
        tiny, programs, path):
    """Through the dense cache, and through a pool the dense cache was
    re-laid into: both the full forward's logits at every step, the
    selection's counters equal."""
    cfg, params = tiny
    step, _ = programs
    if path == "kernel":        # a trace of its own, under the fixture
        step = jax.jit(lambda c, t: glm_dsa_decode_step(params, c, t, cfg))
    toks = _tokens(5, 2, 72)
    want = _forward(cfg, params, toks)
    lg, dense = glm_dsa_prefill(params, jnp.asarray(toks[:, :40]), cfg)
    np.testing.assert_allclose(np.asarray(lg)[:, :512], want[:, 39],
                               atol=F32_ATOL)
    # 2 rows x 3 layers: 1 + .. + 40 reachable, at most 24 of them taken
    assert np.asarray(dense[dc.INDEX]).tolist() == [
        6.0 * (sum(range(1, 25)) + 16 * 24), 6.0 * sum(range(1, 41))]
    paged = dc.dense_to_paged(dense, 16)
    assert dc.positional(paged) == ("ckv", "kpe", "kidx")
    for i in range(40, 72):
        lg, dense = step(dense, jnp.asarray(toks[:, i]))
        lp, paged = step(paged, jnp.asarray(toks[:, i]))
        np.testing.assert_allclose(np.asarray(lg)[:, :512], want[:, i],
                                   atol=F32_ATOL)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lg),
                                   atol=2e-6)
    assert np.asarray(paged[dc.INDEX]).tolist() == [
        6.0 * 24, 6.0 * 72] == np.asarray(dense[dc.INDEX]).tolist()


def _row(blocks, cfg, bs=16):
    bt = np.zeros((cfg.max_seq // bs,), np.int32)
    bt[:len(blocks)] = blocks
    return jnp.asarray(bt)


def _tail(tokens, t_pad):
    out = np.zeros((1, t_pad), np.int32)
    out[0, t_pad - len(tokens):] = tokens
    return jnp.asarray(out)


def test_chunks_and_a_prefix_hit_give_what_one_cold_shot_gives(tiny,
                                                               programs):
    """72 tokens at once, and 32 + 32 + 8; then a second prompt that
    shares the first 64: its tail of 16 scores and selects over the
    resident blocks, whose ``kidx`` rows no one wrote again."""
    cfg, params = tiny
    _, prefill = programs
    seq = _tokens(6, 1, 72)[0]
    want = _forward(cfg, params, seq[None])[0, -1]
    fresh = glm_dsa_init_paged_cache(cfg, 2, num_blocks=20, block_size=16)
    own = _row(range(1, 6), cfg)
    lg, _ = prefill(fresh, _tail(seq, 80), own, 0, 72, 0)
    np.testing.assert_allclose(np.asarray(lg)[:512], want, atol=F32_ATOL)
    cache = fresh
    for at in (0, 32):
        _, cache = prefill(cache, _tail(seq[at:at + 32], 32), own, at, 32, 0)
    lg, cache = prefill(cache, _tail(seq[64:], 32), own, 64, 8, 0)
    np.testing.assert_allclose(np.asarray(lg)[:512], want, atol=F32_ATOL)
    new = _tokens(7, 1, 16)[0]
    cold = _forward(cfg, params, np.concatenate([seq[:64], new])[None])[0, -1]
    shared = np.asarray(cache["kidx"][:, 1:5])
    lg, cache = prefill(cache, _tail(new, 32), _row([1, 2, 3, 4, 9], cfg),
                        64, 16, 1)
    np.testing.assert_allclose(np.asarray(lg)[:512], cold, atol=F32_ATOL)
    np.testing.assert_array_equal(np.asarray(cache["kidx"][:, 1:5]), shared)
    assert np.abs(shared).min(axis=-1).max() > 0         # every row written
    assert int(cache["pos"][1]) == 80
    # three tensors a block: 16 tokens x 3 layers x (32 + 8 + 16) float32
    assert dc.block_bytes(cache) == 16 * 3 * 56 * 4
    assert dc.cache_reach(cache)["pool_bytes_per_token"] == 3 * 56 * 4
    assert dc.kv_shards(cache) == 1


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_generate_is_greedy_under_the_reference(reference, tiny, layout):
    cfg, params = tiny
    prompt = _tokens(8, 2, 40)
    out = np.asarray(glm_dsa_generate(
        params, jnp.asarray(prompt), cfg, max_new_tokens=6,
        temperature=0.0, kv_layout=layout))
    lg = _ref_logits(reference, params, cfg, out[:, :-1])
    assert np.array_equal(lg[:, 39:].argmax(-1), out[:, 40:])


def test_ragged_rows_decode_as_they_would_alone(tiny, path):
    """(Through the kernel the batch decodes from a pool: a row's first
    slot is then past 0, which no engine's row is today.)"""
    cfg, params = tiny
    a, b = _tokens(9, 1, 40)[0], _tokens(10, 1, 29)[0]
    batch = np.zeros((2, 40), np.int32)
    batch[0], batch[1, 11:] = a, b
    both = np.asarray(glm_dsa_generate(
        params, jnp.asarray(batch), cfg, max_new_tokens=5, temperature=0.0,
        lengths=jnp.asarray([40, 29]),
        kv_layout="paged" if path == "kernel" else "dense"))
    alone = np.asarray(glm_dsa_generate(
        params, jnp.asarray(b[None]), cfg, max_new_tokens=5,
        temperature=0.0))
    assert np.array_equal(both[1, 40:], alone[0, 29:])


@pytest.mark.parametrize("program", ["decode_step", "paged_prefill"])
def test_the_indexers_work_stands_under_its_scope(tiny, programs, program):
    """``attn_index`` beside kimi_k2's scopes, at most a tenth of the
    operations outside any; the index products, their ReLU and the
    prefill's loops (key blocks, the search's 32 passes) under it, the
    gathers of all three pools under ``kv_pool``."""
    from tests.test_scopes import _op_scopes

    cfg, params = tiny
    step, prefill = programs
    cache = glm_dsa_init_paged_cache(cfg, 2, num_blocks=20, block_size=16)
    lowered = step.lower(cache, jnp.zeros((2,), jnp.int32)) \
        if program == "decode_step" else prefill.lower(
            cache, jnp.zeros((1, 32), jnp.int32), _row([1, 2], cfg), 0, 32, 0)
    ops = _op_scopes(lowered)
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == {
        "embed", "ln", "mla", "attn_index", "kv_pool", "mlp", "moe_router",
        "moe_experts", "lm_head", "layer_scan"}
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    by_op = collections.defaultdict(set)
    for op, s in ops:
        by_op[op].add(s)
    assert by_op["stablehlo.gather"] <= {"kv_pool", "embed", "moe_router",
                                         "moe_experts"}
    assert "attn_index" in by_op["stablehlo.dot_general"]
    assert "attn_index" in by_op["call @relu"]
    if program == "paged_prefill":          # the search's candidate bit
        assert by_op["stablehlo.shift_left"] == {"attn_index"}
    assert scopes.ATTN_INDEX in scopes.DEVICE_SCOPES
    assert scopes.ATTN_INDEX not in scopes.CONTAINER_SCOPES


# -- the family through the serving engine ------------------------------------

MAX_NEW = 6


def _build(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    return build_llm_deployment("glm_dsa", "nano", temperature=0.0,
                                config_overrides=_OVR, **kw)


def _serve(dep, prompts):
    async def main():
        inst = dep.func_or_class()
        try:
            outs = [await inst(p) for p in prompts]
            hits = [r["kv_reserve"][3] if r.get("kv_reserve") else 0
                    for r in inst.trace_records()]
            return outs, inst.engine_stats(), hits
        finally:
            if hasattr(inst, "_engine_task"):
                inst.shutdown_engine()

    return asyncio.run(main())


A = _tokens(11, 70)
B = np.concatenate([A[:48], _tokens(12, 9)])


@pytest.mark.parametrize("kw", [{}, {"prefill_chunk_tokens": 32}],
                         ids=["paged", "chunked"])
def test_the_engine_answers_as_generate_and_counts_the_selection(tiny, kw):
    """Two prompts past ``index_topk``, the second behind three of the
    first's blocks: the tokens `glm_dsa_generate` gives, the shared
    blocks hit, and the selection's counters landed with the tokens."""
    cfg, params = tiny
    outs, stats, hits = _serve(_build(**kw), [A, B])
    for prompt, out in zip([A, B], outs):
        want = np.asarray(glm_dsa_generate(
            params, jnp.asarray(prompt[None]), cfg, max_new_tokens=MAX_NEW,
            temperature=0.0))[0]
        np.testing.assert_array_equal(out, want)
    assert hits == [0, 3]
    index = stats["index"]
    assert set(index) == {"decode", "prefill"}
    # five decode waves a request, three layers, 24 of 71..75 and 58..62
    assert index["decode"]["programs"] == 2 * (MAX_NEW - 1)
    assert index["decode"]["selected"] == 10 * 3 * 24
    assert index["decode"]["reachable"] == 3 * (sum(range(71, 76))
                                                + sum(range(58, 63)))
    assert 0.3 < index["decode"]["selected_share"] < 0.45
    # A's 70 queries cold, B's 9 behind 48 resident positions
    assert index["prefill"]["reachable"] == 3 * (
        sum(range(1, 71)) + sum(range(49, 58)))
    assert set(stats["experts"]) == {"decode", "prefill"}
    from ray_tpu.util.metrics import _registry

    assert "serve_index_selected_total" in _registry.snapshot()


def test_the_family_is_a_row_and_what_a_latent_pool_refuses_is_refused():
    fam = families.family("glm_dsa")
    assert fam.cache_kind == families.LATENT and fam.verify is None
    for option, kw in [("spec_decode", {"spec_decode": SpecConfig()}),
                       ("kv_host_tier_bytes", {"kv_host_tier_bytes": 1 << 20}),
                       ("role='prefill'", {"role": "prefill"}),
                       ("mesh", {"mesh": object()})]:
        with pytest.raises(ValueError, match="latent pool") as e:
            _build(**kw)
        assert option in str(e.value)
    with pytest.raises(ValueError, match="spec draft"):
        SpecConfig(draft="glm_dsa:nano")
