"""The Solar-Open2 family at ``nano`` on the CPU with seeded weights:
the forward against the plain reference, every cache path against the
full forward, the shares of the experts against the uncut layer, what
its programs call their parts, and the family served by the continuous
engine."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from ray_tpu._private import scopes
from ray_tpu.models import decode_common as dc
from ray_tpu.models import experts as ex
from ray_tpu.models import families
from ray_tpu.models import solar_open2 as so
from ray_tpu.models import solar_open2_decode as m
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_SLOT,
                                          STATE_FROM_ZERO, sample_token)
from ray_tpu.ops import kda
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment
from tests.test_kimi_k2_serve import _serve
from tests.test_scopes import _op_scopes

BS = 8
#: this "chip" holds half of nano's 16 experts
F32 = so.solar_open2_config("nano", dtype=jnp.float32,
                            held=tuple(range(8)))
#: float32 programs against float32 programs or the float32 reference,
#: whose sums run in other orders (the chunked delta rule's above all:
#: a triangular solve a chunk against one token at a time): logits of
#: std 0.16 agree to 1e-6, and every fault below moves them by 4e-4 or
#: more
TOL = 2e-5
REFERENCE = cells._load_module("reference", "solar_open2")


def _stated(cfg):
    return dict(vocab_size=cfg.vocab_size, layer_types=cfg.layer_types,
                n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
                held=cfg.experts.held_ids, top_k=cfg.top_k,
                neg_eigval=cfg.neg_eigval, eps=cfg.rms_eps)


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


@pytest.fixture(scope="module")
def params():
    return so.solar_open2_init(jax.random.PRNGKey(0), F32)


@pytest.fixture(scope="module")
def want(params):
    """The full forward's logits of one sequence of 48 tokens."""
    toks = _tokens(1, 1, 48)
    return toks, np.asarray(jax.jit(lambda p, t: so.solar_open2_forward(
        p, t, F32))(params, jnp.asarray(toks)))[0]


def test_the_nano_preset_is_one_period_and_a_layer():
    assert F32.layer_types == ("gqa", "kda", "kda", "kda", "gqa")
    assert F32.layers_of(so.KDA) == (1, 2, 3)
    tree = jax.eval_shape(lambda: so.solar_open2_init(
        jax.random.PRNGKey(0), F32))
    assert sum(a.size for a in jax.tree.leaves(tree)) \
        == so.solar_open2_param_count(F32)
    axes = so.solar_open2_logical_axes(F32)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, tree))
    full = so.solar_open2_config("solar-open2")
    assert full.layer_types[:8] == ("gqa", "kda", "kda", "kda") * 2
    assert (so.gqa_params(full), so.kda_params(full)) \
        == (109_051_904, 137_732_288)


def test_the_seeded_decays_span_what_the_docstring_says(params):
    """A state that forgets in ten tokens tests nothing at 8k."""
    p = params["layers"][1]["kda"]
    decay = np.exp(-np.exp(np.asarray(p["A_log"]))[:, None]
                   * np.asarray(jax.nn.softplus(p["dt_bias"])))
    lo, hi = so.DECAY_SPAN
    assert lo - 1e-6 <= decay.min() < 0.93 and 0.995 < decay.max() \
        <= hi + 1e-6


def test_a_seeded_kda_mixer_adds_no_vector_every_token_shares():
    """`so.SILU_IN`: at the published input width (2 heads stand for
    64) a seeded KDA mixer's output over unit inputs is the tokens':
    under a hundredth of its energy lies in its mean over 384 tokens
    (1/384 is what no mean at all reads), where taps of N(0, 0.5) leave
    a quarter there, which every later router scores: the held experts'
    load then swings with the seed (PERF.md section 6, PR 49)."""
    cfg = so.solar_open2_config(
        "nano", n_layer=2, gqa_layers=(0,), d_model=4096, kda_heads=2,
        kda_head_dim=128, gate_rank=128, dtype=jnp.float32)
    p = so.solar_open2_init(jax.random.PRNGKey(0), cfg)["layers"][1]["kda"]
    u = jax.random.normal(jax.random.PRNGKey(1), (1, 512, 4096))
    out = so.kda_mix(p, u, cfg, *so.zero_recurrent(
        cfg, 1, layers=False))[0][0, 128:]
    shared = jnp.sum(jnp.mean(out, 0) ** 2) / jnp.mean(jnp.sum(out ** 2, -1))
    assert float(shared) < 0.01
    assert float(jnp.std(p["conv_w"])) == pytest.approx(
        so.SILU_IN / (0.02 * 128), rel=0.1)


def test_the_forward_is_the_reference(params, want):
    toks, logits = want
    ref = REFERENCE.logits(params, jnp.asarray(toks), **_stated(F32))
    np.testing.assert_allclose(logits[:, :F32.vocab_size], ref[0],
                               atol=TOL)
    loss = float(jax.jit(lambda p, t: so.solar_open2_loss(
        p, {"tokens": t}, F32))(params, jnp.asarray(toks)))
    assert abs(loss - float(REFERENCE.loss(
        params, jnp.asarray(toks), **_stated(F32)))) < 1e-5


@pytest.mark.parametrize("lengths", [None, (30, 17)], ids=["even", "ragged"])
def test_prefill_then_decode_through_the_dense_cache(lengths, params):
    """Two rows, the second left-padded: the GQA layer masks the pads'
    keys, the KDA layers step over them."""
    toks = _tokens(2, 2, 40)
    full = jax.jit(lambda p, t: so.solar_open2_forward(p, t, F32))
    n = lengths or (30, 30)
    prompt = np.zeros((2, 30), np.int32)
    for b in range(2):
        prompt[b, 30 - n[b]:] = toks[b, :n[b]]
    logits, cache = jax.jit(lambda p, t: m.solar_open2_prefill(
        p, t, F32, lengths=None if lengths is None
        else jnp.asarray(lengths)))(params, jnp.asarray(prompt))
    step = jax.jit(lambda p, c, t: m.solar_open2_decode_step(p, c, t, F32))
    rows = [np.asarray(full(params, jnp.asarray(toks[b:b + 1])))[0]
            for b in range(2)]
    for k in range(6):
        for b in range(2):
            np.testing.assert_allclose(logits[b], rows[b][n[b] - 1 + k],
                                       atol=TOL)
        logits, cache = step(params, cache, jnp.asarray(
            [toks[b, n[b] + k] for b in range(2)]))


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_generate_equals_the_full_forward(layout, params):
    prompt = _tokens(3, 2, 20)
    out = np.asarray(jax.jit(lambda p, t: m.solar_open2_generate(
        p, t, F32, max_new_tokens=8, temperature=0.0, kv_layout=layout,
        kv_block_size=BS))(params, jnp.asarray(prompt)))
    logits = np.asarray(jax.jit(lambda p, t: so.solar_open2_forward(
        p, t, F32))(params, jnp.asarray(out)))
    np.testing.assert_array_equal(
        out[:, 20:], logits[:, 19:-1, :F32.vocab_size].argmax(-1))


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_decode_steps_through_the_kernel_answer_as_generate(layout,
                                                            monkeypatch):
    """Eight KDA heads of 128, where the decode wave's kernel fits:
    with `kda_decode` held to it (interpreted: the CPU picks `kda_step`)
    the greedy tokens of both cache layouts are `solar_open2_generate`'s
    own, which are the `jnp` form's."""
    import functools

    cfg = so.solar_open2_config("nano", dtype=jnp.float32, kda_heads=8,
                                kda_head_dim=128, held=tuple(range(8)))
    wide = so.solar_open2_init(jax.random.PRNGKey(4), cfg)
    prompt = jnp.asarray(_tokens(5, 2, 12))

    def generate():     # a new program: the patched name is read traced
        return np.asarray(jax.jit(lambda p, t: m.solar_open2_generate(
            p, t, cfg, max_new_tokens=6, temperature=0.0, kv_layout=layout,
            kv_block_size=BS))(wide, prompt))

    want = generate()
    called = []
    monkeypatch.setattr(so, "kda_decode", lambda *a: called.append(
        a[5].shape) or kda.kda_decode(*a, interpret=True))
    np.testing.assert_array_equal(generate(), want)
    # the whole stack goes in, once a KDA layer of the scanned step
    assert called == [(3, 2, 8, 128, 128)] * 3, called


def _paged(slots=3, blocks=40):
    return m.solar_open2_init_paged_cache(F32, slots, num_blocks=blocks,
                                          block_size=BS)


_PREFILL = jax.jit(
    lambda p, c, t, bt, pre, n, slot, state: m.solar_open2_paged_prefill(
        p, c, t, F32, row_bt=bt, prefix_len=pre, n_tail=n, slot=slot,
        state=state))
ROW_BT = jnp.arange(1, 1 + 128 // BS, dtype=jnp.int32)


def _tail(toks, lo, hi, t_pad):
    """toks[lo:hi] right-aligned in `t_pad` columns."""
    out = np.zeros((1, t_pad), np.int32)
    out[0, t_pad - (hi - lo):] = toks[0, lo:hi]
    return jnp.asarray(out), lo, hi - lo


def _state(source=STATE_FROM_ZERO, entry=NO_SNAPSHOT, boundary=0):
    return jnp.asarray([source, entry, boundary], jnp.int32)


@pytest.mark.parametrize("n,t_pad", [(5, 16), (16, 16), (23, 32),
                                     (40, 48), (48, 48)])
def test_paged_prefill_then_decode_equal_the_full_forward(n, t_pad, params,
                                                          want):
    toks, logits = want
    lg, cache = _PREFILL(params, _paged(), *_tail(toks, 0, n, t_pad)[:1],
                         ROW_BT, 0, n, 1, _state())
    np.testing.assert_allclose(lg, logits[n - 1], atol=TOL)
    step = jax.jit(lambda p, c, t: m.solar_open2_decode_step(p, c, t, F32))
    for k in range(n, min(n + 4, 48)):
        lg, cache = step(params, cache, jnp.asarray([0, toks[0, k], 0]))
        np.testing.assert_allclose(lg[1], logits[k], atol=TOL)
    assert int(cache["pos"][0]) == 0            # an idle row stays one


def test_a_prompt_admitted_in_chunks_is_one_shot(params, want):
    """Three pieces of 16, 16 and 8: the matrices and the windows carry
    from piece to piece in the slot's own rows."""
    toks, logits = want
    whole = _PREFILL(params, _paged(), _tail(toks, 0, 40, 48)[0], ROW_BT,
                     0, 40, 2, _state())[1]
    cache = _paged()
    for lo, hi, source in ((0, 16, STATE_FROM_ZERO),
                           (16, 32, STATE_FROM_SLOT),
                           (32, 40, STATE_FROM_SLOT)):
        tail, pre, n = _tail(toks, lo, hi, 16)
        lg, cache = _PREFILL(params, cache, tail, ROW_BT, pre, n, 2,
                             _state(source))
    np.testing.assert_allclose(lg, logits[39], atol=TOL)
    for name in ("ssm", "conv"):
        np.testing.assert_allclose(cache[name], whole[name], atol=TOL)


def test_a_prefix_hit_starts_from_its_snapshot(params, want):
    """A prompt leaves the state after its block boundary (24 tokens) in
    snapshot entry 1; another slot's prompt with those 24 resident
    starts from it and reads the logits a cold prompt reads."""
    toks, logits = want
    _, cache = _PREFILL(params, _paged(), _tail(toks, 0, 29, 32)[0],
                        ROW_BT, 0, 29, 0, _state(entry=1, boundary=24))
    cold = _PREFILL(params, _paged(), _tail(toks, 0, 24, 32)[0], ROW_BT, 0,
                    24, 0, _state())[1]
    for name, axis in (("ssm", 1), ("conv", 2)):
        np.testing.assert_allclose(
            jnp.take(cache["snap_" + name], 1, axis=axis),
            jnp.take(cold[name], 0, axis=axis), atol=TOL)
    tail, pre, n = _tail(toks, 24, 40, 16)
    lg, hit = _PREFILL(params, cache, tail, ROW_BT, pre, n, 2,
                       _state(source=1))
    np.testing.assert_allclose(lg, logits[39], atol=TOL)
    # the engine's other road: the entry copied into the row at once,
    # the chunks run later from the slot's own rows
    restored = dc.restore_state(cache, 1, 2)
    lg, _ = _PREFILL(params, restored, tail, ROW_BT, pre, n, 2,
                     _state(STATE_FROM_SLOT))
    np.testing.assert_allclose(lg, logits[39], atol=TOL)
    assert dc.state_bytes(hit) == 2 * 3 * 3 * (
        4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)


def test_an_idle_or_parked_row_keeps_its_state(params, want):
    toks, _ = want
    _, cache = _PREFILL(params, _paged(), _tail(toks, 0, 20, 32)[0],
                        ROW_BT, 0, 20, 1, _state())
    parked = dc.clear_row(cache, 1)
    after = jax.jit(lambda p, c, t: m.solar_open2_decode_step(
        p, c, t, F32))(params, parked, jnp.asarray([3, 4, 5]))[1]
    for name in ("ssm", "conv"):
        assert bool(jnp.all(after[name] == cache[name]))
    assert after["pos"].tolist() == [0, 0, 0]


def test_the_shares_add_up(params):
    """nano's 16 experts over 4 chips: the routed parts that the four
    shares of a layer compute, the shared expert counted once, equal
    the uncut reference's layer."""
    whole = so.solar_open2_config("nano", dtype=jnp.float32)
    p = ex.experts_init(jax.random.PRNGKey(5), whole.experts)
    x = jax.random.normal(jax.random.PRNGKey(6), (24, whole.d_model))
    want = REFERENCE._experts(x, p, tuple(range(16)), whole.top_k, True,
                              1.0)
    shared = ex.shared_expert(p["shared"], x, whole.experts)
    total = shared.astype(jnp.float32)
    for share in range(4):
        held = ex.held_range(4 * share, 4)
        cfg = so.solar_open2_config("nano", dtype=jnp.float32,
                                    held=held).experts
        mine = dict(p, experts=jax.tree.map(
            lambda a: a[4 * share:4 * share + 4], p["experts"]))
        y, _ = ex.moe_layer(mine, x, cfg)
        total = total + (y.astype(jnp.float32) - shared)
        part = REFERENCE._experts(x, mine, held, whole.top_k, True, 1.0)
        np.testing.assert_allclose(y, part, atol=TOL)
    np.testing.assert_allclose(total, want, atol=TOL)


def _bf16_state(q, k, v, g, beta, stack, j):
    o, stack = kda.kda_decode(q, k, v, g, beta, stack, j)
    return o, stack.at[j].set(
        stack[j].astype(jnp.bfloat16).astype(jnp.float32))


def _no_carried_state(*args, **kw):
    """A chunked form that forgets ``K+ S_0``: every chunk's right-hand
    side as if the state entering were zero."""
    real = kda._chunk

    def chunk(q, k, v, g, beta, s0, *rest):
        o, s, snap = real(q, k, v, g, beta, jnp.zeros_like(s0), *rest)
        lift = jnp.exp(jnp.sum(g, axis=-2))[..., None] * s0
        return o, s + lift, None if snap is None else snap + lift

    kda._chunk = chunk
    try:
        return kda.kda_chunked(*args, **kw)
    finally:
        kda._chunk = real


@pytest.mark.parametrize("fault", ["bf16_state", "no_carried_state",
                                   "beta_not_doubled", "no_gqa_gate"])
def test_a_wrong_model_fails_the_tolerance(fault, params, want,
                                           monkeypatch):
    """What `TOL` sees, each twenty times it or more: the matrices
    rounded to bf16 after every decode step (5.1e-4 over four steps), a
    chunked form without its carried-in state (0.14), beta left in
    (0, 1) (0.10), the softmax layer's gate left out (7.1e-3)."""
    toks, logits = want
    cfg = F32
    if fault == "bf16_state":
        monkeypatch.setattr(so, "kda_decode", _bf16_state)
    elif fault == "no_carried_state":
        monkeypatch.setattr(so, "kda_prefill", _no_carried_state)
    elif fault == "beta_not_doubled":
        cfg = so.solar_open2_config("nano", dtype=jnp.float32,
                                    held=tuple(range(8)), neg_eigval=False)
    else:
        monkeypatch.setattr(so, "attn_out", _ungated)
    # the patched names are read when a program is traced: new programs
    prefill = jax.jit(lambda p, c, t: m.solar_open2_paged_prefill(
        p, c, t, cfg, row_bt=ROW_BT, prefix_len=0, n_tail=40, slot=1,
        state=_state()))
    step = jax.jit(lambda p, c, t: m.solar_open2_decode_step(p, c, t, cfg))
    lg, cache = prefill(params, m.solar_open2_init_paged_cache(
        cfg, 3, num_blocks=40, block_size=BS), _tail(toks, 0, 40, 48)[0])
    worst = float(np.abs(lg - logits[39]).max())
    for k in range(40, 44):
        lg, cache = step(params, cache, jnp.asarray([0, toks[0, k], 0]))
        worst = max(worst, float(np.abs(lg[1] - logits[k]).max()))
    assert worst > 20 * TOL, worst


def _ungated(o, gate, p, c):
    from ray_tpu.models import banded_attention

    return banded_attention.attn_out(o, jnp.ones_like(gate), p, c)


# -- what the programs call their parts ---------------------------------------

EVERY = {"embed", "ln", "attn_full", "attn_linear", "linear_state",
         "kv_pool", "mlp", "moe_router", "moe_experts", "lm_head", "sample"}


def _lowered(name, params):
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731

    def pool_step(p, cache, toks, key):
        logits, cache = m.solar_open2_decode_step(p, cache, toks, F32)
        return sample_token(logits, key, 0.0, None), cache

    def prefill_sample(p, cache, toks, row_bt, key, state):
        logits, cache = m.solar_open2_paged_prefill(
            p, cache, toks, F32, row_bt=row_bt, prefix_len=0, n_tail=21,
            slot=0, state=state)
        return sample_token(logits[None], key, 0.0, None), cache

    if name == "decode_step":
        return jax.jit(pool_step).lower(params, _paged(2, 20), i32(2), key)
    return jax.jit(prefill_sample).lower(
        params, _paged(2, 20), i32(1, 32), i32(128 // BS), key, i32(3))


@pytest.mark.parametrize("program", ["decode_step", "paged_prefill"])
def test_the_new_scopes_hold_the_mixers_and_their_state(program, params):
    assert {scopes.ATTN_LINEAR, scopes.LINEAR_STATE} <= scopes.DEVICE_SCOPES
    ops = _op_scopes(_lowered(program, params))
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == EVERY
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    heavy = {"stablehlo.dot_general", "stablehlo.exponential",
             "stablehlo.gather", "stablehlo.scatter", "chlo.ragged_dot"}
    assert not heavy & set(loose), collections.Counter(loose)
    # the decays' exponentials are the delta rule's; the softmax's the
    # one GQA kind's
    exps = collections.Counter(s for op, s in ops
                               if op == "stablehlo.exponential")
    assert exps[scopes.ATTN_LINEAR] and exps[scopes.ATTN_FULL]
    moved = collections.Counter(
        s for op, s in ops if op in ("stablehlo.dynamic_update_slice",
                                     "stablehlo.dynamic_slice",
                                     "stablehlo.scatter"))
    assert moved[scopes.LINEAR_STATE]


# -- the engine's normal path -------------------------------------------------

MAX_NEW = 6
_OVR = {"dtype": jnp.float32}
A = _tokens(11, 40)
B = np.concatenate([A[:32], _tokens(12, 5)])
C = _tokens(13, 21)
D = _tokens(14, 5)


def _build(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_bucket", 64)          # one prefill program
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    return build_llm_deployment("solar_open2", "nano", temperature=0.0,
                                config_overrides=_OVR, **kw)


_ORACLE = {}


def _oracle(prompt):
    """`generate`'s answer to `prompt`, left-padded to 40 columns so
    that one program answers every prompt."""
    if "fn" not in _ORACLE:
        cfg = so.solar_open2_config("nano", **_OVR)
        weights = so.solar_open2_init(jax.random.PRNGKey(0), cfg)
        generate = jax.jit(lambda p, t, n: m.solar_open2_generate(
            p, t, cfg, max_new_tokens=MAX_NEW, temperature=0.0, lengths=n))
        _ORACLE["fn"] = lambda t, n: generate(weights, t, n)
    padded = np.zeros((1, 40), np.int32)
    padded[0, 40 - len(prompt):] = prompt
    out = np.asarray(_ORACLE["fn"](jnp.asarray(padded),
                                   jnp.asarray([len(prompt)])))[0]
    return out[40 - len(prompt):]


@pytest.mark.parametrize("kw", [{}, {"prefill_chunk_tokens": 16},
                                {"kv_layout": "dense"}],
                         ids=["paged", "chunked", "dense"])
def test_the_engine_answers_as_generate(kw):
    """A repeats: its second admission hits two blocks and the state's
    snapshot at their boundary (paged), and answers as the cold one; B
    shares 32 tokens with A and starts from the same snapshot."""
    prompts = [A, C, D, A, B]
    outs, stats, hits = _serve(_build(**kw), prompts)
    for prompt, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["requests"]["finished"] == 5
    if kw.get("kv_layout") != "dense":
        assert hits == [0, 0, 0, 2, 2]
        assert stats["recurrent"]["snapshot_hits"] == 2
        # three slots and their snapshots: three layers' matrices and
        # windows each
        assert stats["recurrent"]["state_bytes"] == 2 * 3 * 3 * (
            4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
        experts = stats["experts"]
        assert experts["decode"]["held"] == experts["decode"]["of"] == 16


@pytest.mark.parametrize("kw,option", [
    ({"spec_decode": SpecConfig(draft="ngram", k=2)}, "spec_decode"),
    ({"kv_host_tier_bytes": 1 << 20}, "kv_host_tier_bytes"),
    ({"role": "prefill"}, "role='prefill'"),
    ({"mesh": object()}, "mesh")])
def test_what_cannot_carry_the_matrices_is_refused(kw, option):
    assert families.cache_kind("solar_open2") == families.RECURRENT
    with pytest.raises(ValueError) as e:
        _build(**kw)
    assert "family 'solar_open2' keeps a kv+recurrent cache" in str(e.value)
    assert f"{option} cannot carry yet" in str(e.value)
