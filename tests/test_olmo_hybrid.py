"""The Olmo-Hybrid family at ``nano`` on the CPU with seeded weights:
the forward against the plain reference, every cache path against the
full forward, what its programs call their parts, and the family served
by the continuous engine.  Nothing but depth is cut in its cell, so
there is no share to add up."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from ray_tpu._private import scopes
from ray_tpu.models import decode_common as dc
from ray_tpu.models import families
from ray_tpu.models import olmo_hybrid as oh
from ray_tpu.models import olmo_hybrid_decode as m
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_SLOT,
                                          STATE_FROM_ZERO, sample_token)
from ray_tpu.ops import kda
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment
from tests.test_kimi_k2_serve import _serve
from tests.test_scopes import _op_scopes

BS = 8
F32 = oh.olmo_hybrid_config("nano", dtype=jnp.float32)
#: float32 programs against float32 programs or the float32 reference,
#: whose sums run in other orders (the chunked delta rule's above all:
#: a triangular solve a chunk against one token at a time; and every
#: sublayer's output is NORMED, which passes a relative error on
#: whole): logits of std 0.14 agree to 6e-6, and every fault below
#: moves them by 1e-3 or more
TOL = 3e-5
REFERENCE = cells._load_module("reference", "olmo_hybrid")


def _stated(cfg):
    return dict(vocab_size=cfg.vocab_size, layer_types=cfg.layer_types,
                n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
                neg_eigval=cfg.neg_eigval, eps=cfg.rms_eps)


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


@pytest.fixture(scope="module")
def params():
    return oh.olmo_hybrid_init(jax.random.PRNGKey(0), F32)


_FORWARD = jax.jit(lambda p, t: oh.olmo_hybrid_forward(p, t, F32))
_STEP = jax.jit(lambda p, c, t: m.olmo_hybrid_decode_step(p, c, t, F32))


@pytest.fixture(scope="module")
def want(params):
    """The full forward's logits of one sequence of 48 tokens."""
    toks = _tokens(1, 1, 48)
    return toks, np.asarray(_FORWARD(params, jnp.asarray(toks)))[0]


def test_the_nano_preset_is_two_periods_of_unequal_heads():
    """What the cell's shapes ask of a small one: both kinds in the
    published 3 : 1, keys and values of different sizes, a head count
    that is no multiple of 8, as many K/V heads as query heads."""
    assert F32.layer_types == ((oh.LINEAR,) * 3 + (oh.FULL,)) * 2
    assert F32.layers_of(oh.FULL) == (3, 7)
    assert F32.lin_key_dim != F32.lin_value_dim and F32.lin_heads % 8
    assert F32.n_kv_head == F32.n_head
    tree = jax.eval_shape(lambda: oh.olmo_hybrid_init(
        jax.random.PRNGKey(0), F32))
    assert sum(a.size for a in jax.tree.leaves(tree)) \
        == oh.olmo_hybrid_param_count(F32)
    axes = oh.olmo_hybrid_logical_axes(F32)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, tree))
    full = oh.olmo_hybrid_config()
    assert full.layer_types[:8] == F32.layer_types
    assert (oh.linear_params(full), oh.full_params(full)) \
        == (88_750_332, 58_990_080)
    assert oh.olmo_hybrid_param_count(full) == 7_430_870_688
    assert (full.conv_width, full.kv_width) == (11_520, 3_840)


def test_the_seeded_draw_keeps_a_state_that_remembers(params):
    """A head's decay spans `DECAY_SPAN` at its bias, and a token moves
    its rate by about `RATE_SWING` of itself whatever the layer: the
    weights that read the un-normed stream are drawn against what the
    stream measures there (`oh.stream_rms`)."""
    lo, hi = oh.DECAY_SPAN
    for i in F32.layers_of(oh.LINEAR):
        p = params["layers"][i]["lin"]
        decay = np.exp(-np.exp(np.asarray(p["A_log"]))
                       * np.asarray(jax.nn.softplus(p["dt_bias"])))
        assert lo - 1e-6 <= decay.min() and decay.max() <= hi + 1e-6
        swing = float(jnp.std(p["wa"])) * np.sqrt(F32.d_model) \
            * oh.stream_rms(2 * i)
        assert swing == pytest.approx(oh.RATE_SWING, rel=0.35)
    # the stream before the last layer measures what the draw expects
    toks = jnp.asarray(_tokens(7, 4, 64))
    cut = oh.olmo_hybrid_config("nano", dtype=jnp.float32, n_layer=7)
    hidden = jax.jit(lambda p, t: oh.olmo_hybrid_hidden(p, t, cut))(
        dict(params, layers=params["layers"][:7]), toks)
    assert float(jnp.sqrt(jnp.mean(hidden ** 2))) == pytest.approx(
        oh.stream_rms(14), rel=0.25)


def test_the_forward_is_the_reference(params, want):
    toks, logits = want
    ref = REFERENCE.logits(params, jnp.asarray(toks), **_stated(F32))
    np.testing.assert_allclose(logits[:, :F32.vocab_size], ref[0],
                               atol=TOL)
    loss = float(jax.jit(lambda p, t: oh.olmo_hybrid_loss(
        p, {"tokens": t}, F32))(params, jnp.asarray(toks)))
    assert abs(loss - float(REFERENCE.loss(
        params, jnp.asarray(toks), **_stated(F32)))) < 1e-5


@pytest.mark.parametrize("lengths", [None, (30, 17)], ids=["even", "ragged"])
def test_prefill_then_decode_through_the_dense_cache(lengths, params):
    """Two rows, the second left-padded: the full layers mask the pads'
    keys, the linear layers step over them."""
    toks = _tokens(2, 2, 40)
    n = lengths or (30, 30)
    prompt = np.zeros((2, 30), np.int32)
    for b in range(2):
        prompt[b, 30 - n[b]:] = toks[b, :n[b]]
    logits, cache = jax.jit(lambda p, t: m.olmo_hybrid_prefill(
        p, t, F32, lengths=None if lengths is None
        else jnp.asarray(lengths)))(params, jnp.asarray(prompt))
    rows = np.asarray(_FORWARD(params, jnp.asarray(toks)))
    for k in range(4):
        for b in range(2):
            np.testing.assert_allclose(logits[b], rows[b][n[b] - 1 + k],
                                       atol=TOL)
        logits, cache = _STEP(params, cache, jnp.asarray(
            [toks[b, n[b] + k] for b in range(2)]))


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_generate_equals_the_full_forward(layout, params):
    prompt = _tokens(3, 2, 20)
    out = np.asarray(jax.jit(lambda p, t: m.olmo_hybrid_generate(
        p, t, F32, max_new_tokens=8, temperature=0.0, kv_layout=layout,
        kv_block_size=BS))(params, jnp.asarray(prompt)))
    logits = np.asarray(_FORWARD(params, jnp.asarray(out)))
    np.testing.assert_array_equal(
        out[:, 20:], logits[:, 19:-1, :F32.vocab_size].argmax(-1))


def _paged(slots=3, blocks=40):
    return m.olmo_hybrid_init_paged_cache(F32, slots, num_blocks=blocks,
                                          block_size=BS)


_PREFILL = jax.jit(
    lambda p, c, t, bt, pre, n, slot, state: m.olmo_hybrid_paged_prefill(
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


@pytest.mark.parametrize("n,t_pad", [(5, 16), (23, 48), (40, 48), (48, 48)])
def test_paged_prefill_then_decode_equal_the_full_forward(n, t_pad, params,
                                                          want):
    """Prompts shorter than a chunk of the rule, of two and a half and
    of three whole (48 columns are three chunks of 16)."""
    toks, logits = want
    lg, cache = _PREFILL(params, _paged(), *_tail(toks, 0, n, t_pad)[:1],
                         ROW_BT, 0, n, 1, _state())
    np.testing.assert_allclose(lg, logits[n - 1], atol=TOL)
    for k in range(n, min(n + 3, 48)):
        lg, cache = _STEP(params, cache, jnp.asarray([0, toks[0, k], 0]))
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
    """A prompt leaves the state after its block boundary (24 tokens,
    inside the second chunk of the rule) in snapshot entry 1; another
    slot's prompt with those 24 resident starts from it and reads the
    logits a cold prompt reads."""
    toks, logits = want
    _, cache = _PREFILL(params, _paged(), _tail(toks, 0, 29, 48)[0],
                        ROW_BT, 0, 29, 0, _state(entry=1, boundary=24))
    cold = _PREFILL(params, _paged(), _tail(toks, 0, 24, 48)[0], ROW_BT, 0,
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
    # three slots and their snapshots: six layers' matrices and windows
    assert dc.state_bytes(hit) == 2 * 3 * 6 * (
        3 * 8 * 16 * 4 + 3 * (2 * 24 + 48) * 4)


def test_an_idle_or_parked_row_keeps_its_state(params, want):
    toks, _ = want
    _, cache = _PREFILL(params, _paged(), _tail(toks, 0, 20, 48)[0],
                        ROW_BT, 0, 20, 1, _state())
    parked = dc.clear_row(cache, 1)
    after = _STEP(params, parked, jnp.asarray([3, 4, 5]))[1]
    for name in ("ssm", "conv"):
        assert bool(jnp.all(after[name] == cache[name]))
    assert after["pos"].tolist() == [0, 0, 0]


def _bf16_state(q, k, v, g, beta, stack, j):
    o, stack = kda.kda_decode(q, k, v, g, beta, stack, j)
    return o, stack.at[j].set(
        stack[j].astype(jnp.bfloat16).astype(jnp.float32))


def _no_seen_term(q, k, v, g, beta, state=None, **kw):
    """A rule that writes ``beta k v^T`` without taking off what the
    decayed state already answers to ``k`` (no ``S'^T k``): linear
    attention with a decay."""
    def token(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s + k_t[..., None] * (
            b_t[..., None] * v_t)[..., None, :]
        return s, jnp.sum(s * q_t[..., None], axis=-2)

    s, o = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s, s


def _input_normed(x, y, scale, cfg, scope):
    """The block every other family has: the norm's weight on nothing,
    the sublayer's output added as it is."""
    return x + y.astype(x.dtype)


@pytest.mark.parametrize("fault", ["bf16_state", "no_seen_term",
                                   "beta_not_doubled", "no_output_norm"])
def test_a_wrong_model_fails_the_tolerance(fault, params, want,
                                           monkeypatch):
    """What `TOL` sees, each thirty times it or more: the matrices
    rounded to bf16 after every decode step (3.5e-3 over four steps), a
    rule without its ``S'^T k`` term (0.72), beta left in (0, 1)
    (0.66), the sublayers' outputs added un-normed (0.75)."""
    toks, logits = want
    cfg = F32
    if fault == "bf16_state":
        monkeypatch.setattr(oh, "kda_decode", _bf16_state)
    elif fault == "no_seen_term":
        monkeypatch.setattr(oh, "kda_prefill", _no_seen_term)
    elif fault == "beta_not_doubled":
        cfg = oh.olmo_hybrid_config("nano", dtype=jnp.float32,
                                    neg_eigval=False)
    else:
        monkeypatch.setattr(oh, "_close", _input_normed)
    # the patched names are read when a program is traced: new programs
    prefill = jax.jit(lambda p, c, t: m.olmo_hybrid_paged_prefill(
        p, c, t, cfg, row_bt=ROW_BT, prefix_len=0, n_tail=40, slot=1,
        state=_state()))
    step = jax.jit(lambda p, c, t: m.olmo_hybrid_decode_step(p, c, t, cfg))
    lg, cache = prefill(params, m.olmo_hybrid_init_paged_cache(
        cfg, 3, num_blocks=40, block_size=BS), _tail(toks, 0, 40, 48)[0])
    worst = float(np.abs(lg - logits[39]).max())
    for k in range(40, 44):
        lg, cache = step(params, cache, jnp.asarray([0, toks[0, k], 0]))
        worst = max(worst, float(np.abs(lg[1] - logits[k]).max()))
    assert worst > 30 * TOL, worst


# -- what the programs call their parts ---------------------------------------

EVERY = {"embed", "attn_full", "attn_linear", "linear_state", "kv_pool",
         "mlp", "lm_head", "sample"}


def _lowered(name, params):
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731

    def pool_step(p, cache, toks, key):
        logits, cache = m.olmo_hybrid_decode_step(p, cache, toks, F32)
        return sample_token(logits, key, 0.0, None), cache

    def prefill_sample(p, cache, toks, row_bt, key, state):
        logits, cache = m.olmo_hybrid_paged_prefill(
            p, cache, toks, F32, row_bt=row_bt, prefix_len=0, n_tail=21,
            slot=0, state=state)
        return sample_token(logits[None], key, 0.0, None), cache

    if name == "decode_step":
        return jax.jit(pool_step).lower(params, _paged(2, 20), i32(2), key)
    return jax.jit(prefill_sample).lower(
        params, _paged(2, 20), i32(1, 32), i32(128 // BS), key, i32(3))


@pytest.mark.parametrize("program", ["decode_step", "paged_prefill"])
def test_a_second_rule_lies_under_the_scopes_the_first_made(program,
                                                            params):
    """No new scope: the output norms lie under the sublayer they close
    (no ``ln`` anywhere), the rule under ``attn_linear``, the matrices'
    and windows' moves under ``linear_state``."""
    ops = _op_scopes(_lowered(program, params))
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == EVERY
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    heavy = {"stablehlo.dot_general", "stablehlo.exponential",
             "stablehlo.gather", "stablehlo.scatter", "stablehlo.rsqrt"}
    assert not heavy & set(loose), collections.Counter(loose)
    exps = collections.Counter(s for op, s in ops
                               if op == "stablehlo.exponential")
    assert exps[scopes.ATTN_LINEAR] and exps[scopes.ATTN_FULL]
    # a norm closes each kind of sublayer under that sublayer's name
    norms = collections.Counter(s for op, s in ops
                                if op == "stablehlo.rsqrt")
    assert {scopes.ATTN_LINEAR, scopes.ATTN_FULL, scopes.MLP} <= set(norms)
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
    return build_llm_deployment("olmo_hybrid", "nano", temperature=0.0,
                                config_overrides=_OVR, **kw)


_ORACLE = {}


def _oracle(prompt):
    """`generate`'s answer to `prompt`, left-padded to 40 columns so
    that one program answers every prompt."""
    if "fn" not in _ORACLE:
        weights = oh.olmo_hybrid_init(jax.random.PRNGKey(0), F32)
        generate = jax.jit(lambda p, t, n: m.olmo_hybrid_generate(
            p, t, F32, max_new_tokens=MAX_NEW, temperature=0.0, lengths=n))
        _ORACLE["fn"] = lambda t, n: generate(weights, t, n)
    padded = np.zeros((1, 40), np.int32)
    padded[0, 40 - len(prompt):] = prompt
    out = np.asarray(_ORACLE["fn"](jnp.asarray(padded),
                                   jnp.asarray([len(prompt)])))[0]
    return out[40 - len(prompt):]


@pytest.mark.parametrize("kw", [{}, {"prefill_chunk_tokens": 16}],
                         ids=["paged", "chunked"])
def test_the_engine_answers_as_generate(kw):
    """A repeats: its second admission hits two blocks and the state's
    snapshot at their boundary, and answers as the cold one; B shares 32
    tokens with A and starts from the same snapshot."""
    prompts = [A, C, D, A, B]
    outs, stats, hits = _serve(_build(**kw), prompts)
    for prompt, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["requests"]["finished"] == 5
    assert hits == [0, 0, 0, 2, 2]
    assert stats["recurrent"]["snapshot_hits"] == 2
    assert stats["recurrent"]["state_bytes"] == 2 * 3 * 6 * (
        3 * 8 * 16 * 4 + 3 * (2 * 24 + 48) * 4)
    assert "experts" not in stats or not stats["experts"]


@pytest.mark.parametrize("kw,option", [
    ({"spec_decode": SpecConfig(draft="ngram", k=2)}, "spec_decode"),
    ({"kv_host_tier_bytes": 1 << 20}, "kv_host_tier_bytes"),
    ({"role": "prefill"}, "role='prefill'"),
    ({"mesh": object()}, "mesh")])
def test_what_cannot_carry_the_matrices_is_refused(kw, option):
    assert families.cache_kind("olmo_hybrid") == families.RECURRENT
    with pytest.raises(ValueError) as e:
        _build(**kw)
    assert "family 'olmo_hybrid' keeps a kv+recurrent cache" in str(e.value)
    assert f"{option} cannot carry yet" in str(e.value)
