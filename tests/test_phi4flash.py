"""The Phi-4-mini-flash family at ``nano`` on the CPU with seeded
weights: the forward against the plain reference, the pair-head form of
differential attention against the four-softmax form, every cache path
against the full forward, a prefill whose cross-decoder sees one token,
what its programs call their parts, and the family served by the
continuous engine."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells
from ray_tpu._private import scopes
from ray_tpu.models import decode_common as dc
from ray_tpu.models import families
from ray_tpu.models import phi4flash as ph
from ray_tpu.models import phi4flash_decode as m
from ray_tpu.models.decode_common import (NO_SNAPSHOT, STATE_FROM_SLOT,
                                          STATE_FROM_ZERO, sample_token)
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment
from tests.test_kimi_k2_serve import _serve
from tests.test_scopes import _op_scopes

BS = 8
F32 = ph.phi4flash_config("nano", dtype=jnp.float32)
#: float32 programs against float32 programs or the float32 reference,
#: whose sums run in other orders (blocks of queries, a running softmax
#: over key tiles, padded queries that add exact zeros): logits of std
#: 0.16 agree to 3e-7, and every fault below moves them by 1e-4 or more
TOL = 5e-6
REFERENCE = cells._load_module("reference", "phi4flash")
STATED = dict(vocab_size=F32.vocab_size, n_head=F32.n_head,
              n_kv_head=F32.n_kv_head, window=F32.window, eps=F32.ln_eps)
STATE = ("ssm", "conv", "wk", "wv")


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


@pytest.fixture(scope="module")
def params():
    """Seeded weights, the Mamba layers' ``in_proj`` times 4 and
    ``x_proj`` times 8.  As drawn, a width of 64 leaves ``x`` at 0.1 and
    ``B``, ``C`` at 0.02 (no norm lifts them, as Jamba's does): the
    state's part of ``y`` is 1e-5 of the ``D`` skip's and a test of the
    carried state would test nothing.  At the published width the draw
    itself gives ``x``, ``B``, ``C`` of order one."""
    tree = ph.phi4flash_init(jax.random.PRNGKey(0), F32)
    for layer in (tree["self"]["mamba"], tree["memory"]):
        layer["mixer"]["in_proj"] = layer["mixer"]["in_proj"] * 4
        layer["mixer"]["x_proj"] = layer["mixer"]["x_proj"] * 8
    return tree


@pytest.fixture(scope="module")
def want(params):
    """The full forward's logits of one sequence of 48 tokens: six
    windows of 8."""
    toks = _tokens(1, 1, 48)
    return toks, np.asarray(jax.jit(
        lambda p, t: ph.phi4flash_forward(p, t, F32))(params, toks))[0]


def test_the_nano_preset_keeps_the_structure():
    """Three (Mamba, window) pairs, the memory layer, the full layer and
    two (GMU, cross) pairs; a window shorter than the prompts below; at
    the published sizes 3.85 B parameters, 24.2 MB of state a slot."""
    assert (F32.n_self, F32.n_mamba, F32.n_cross) == (3, 4, 2)
    assert F32.layer_index("window").tolist() == [1, 3, 5]
    assert F32.layer_index("full").tolist() == [7]
    assert F32.layer_index("cross").tolist() == [9, 11]
    big = ph.phi4flash_config()
    assert (big.n_self, big.n_mamba, big.n_cross) == (8, 9, 7)
    assert big.layer_index("cross").tolist() == list(range(19, 32, 2))
    assert (big.pairs.n_kv_head, big.pairs.head_dim) == (10, 128)
    assert big.pairs.scale == 0.125
    tree = jax.eval_shape(lambda: ph.phi4flash_init(jax.random.PRNGKey(0),
                                                    big))
    assert sum(a.size for a in jax.tree.leaves(tree)) \
        == ph.phi4flash_param_count(big) == 3_852_562_944
    assert big.state_bytes_per_slot == 3_225_600 + 20_971_520
    np.testing.assert_allclose(big.lambda_init("full"),
                               0.8 - 0.6 * np.exp(-0.3 * 17), rtol=1e-6)
    axes = ph.phi4flash_logical_axes(F32)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(
            lambda a: 0, ph.phi4flash_init(jax.random.PRNGKey(0), F32)))


def test_the_seeded_lambdas_leave_the_second_softmax_its_weight(params):
    """N(0, 0.1) vectors of 8: ``lam`` stays within 0.1 of ``lam_init``,
    between 0.2 and 0.8, so a dropped ``lam o2`` term moves the logits."""
    for stack, kind in ((params["self"]["window"], "window"),
                        (params["cross"]["attn"], "cross")):
        a = stack["attn"]
        lam = jnp.exp(jnp.sum(a["lq1"] * a["lk1"], -1)) \
            - jnp.exp(jnp.sum(a["lq2"] * a["lk2"], -1)) \
            + F32.lambda_init(kind)
        assert float(jnp.max(jnp.abs(lam - F32.lambda_init(kind)))) < 0.2
        assert 0.2 < float(jnp.min(lam)) and float(jnp.max(lam)) < 0.9


def test_the_forward_is_the_reference(params, want):
    """Logits at every position, both rows of a batch."""
    toks = _tokens(2, 2, 40)
    got = jax.jit(lambda p, t: ph.phi4flash_forward(p, t, F32))(params, toks)
    ref = REFERENCE.logits(params, toks, **STATED)
    assert ref.shape == (2, 40, 512) and float(ref.std()) > 0.1
    np.testing.assert_allclose(got, ref, atol=TOL)
    np.testing.assert_allclose(
        REFERENCE.loss(params, toks, **STATED),
        ph.phi4flash_loss(params, {"tokens": jnp.asarray(toks)}, F32),
        rtol=1e-5)


@pytest.mark.parametrize("window", [8, None], ids=["windowed", "full"])
def test_the_pair_head_form_is_the_four_softmax_form(window, params):
    """Grouped-query attention over pair-heads with zero-padded queries,
    then the combine, against the reference's softmax a sub-head a
    query pair: one layer's attention on random inputs."""
    p = jax.tree.map(lambda a: a[1], params["self"]["window"]["attn"])
    u = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    lam_init = float(F32.lambda_init("window")[1])
    q = ph.project_q(u, p, F32)
    k, v = ph.project_kv(u, p, F32)
    assert q.shape == (2, 24, 8, 16)
    assert bool(jnp.all(q[:, :, 0::2, 8:] == 0)) \
        and bool(jnp.all(q[:, :, 1::2, :8] == 0))
    mask = ph.causal_mask(24, window)[None]
    got = ph.diff_out(ph.attend_masked(q, k, v, mask, F32), p, lam_init,
                      F32)
    ref = REFERENCE._differential(
        REFERENCE._queries(u, p, 8), *REFERENCE._keys_values(u, p, 4), p,
        lam_init, window, F32.ln_eps)
    assert float(jnp.std(ref)) > 1e-3
    np.testing.assert_allclose(got, ref, atol=1e-6)


@pytest.mark.parametrize("lengths", [None, (30, 17)], ids=["even", "ragged"])
def test_prefill_then_decode_through_the_dense_cache(lengths, params):
    """Two rows, 30 prompt columns and 14 decode steps: more tokens than
    the window of 8 either way."""
    toks = _tokens(4, 2, 44)
    n = (30, 30) if lengths is None else lengths
    fwd = jax.jit(lambda p, t: ph.phi4flash_forward(p, t, F32))
    wants = [np.asarray(fwd(params, toks[b:b + 1, 30 - n[b]:]))[0]
             for b in range(2)]
    lg, cache = jax.jit(lambda p, t: m.phi4flash_prefill(
        p, t, F32, lengths=None if lengths is None else jnp.asarray(lengths))
    )(params, toks[:, :30])
    step = jax.jit(lambda p, c, t: m.phi4flash_decode_step(p, c, t, F32))
    for k in range(30, 44):
        for b in range(2):
            np.testing.assert_allclose(lg[b], wants[b][n[b] + k - 31],
                                       atol=TOL)
        lg, cache = step(params, cache, toks[:, k])


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_generate_equals_the_full_forward(layout, params):
    prompt = _tokens(5, 2, 19)
    out = np.asarray(jax.jit(lambda p, t: m.phi4flash_generate(
        p, t, F32, max_new_tokens=13, temperature=0.0, kv_layout=layout,
        kv_block_size=BS))(params, prompt))
    logits = np.asarray(jax.jit(
        lambda p, t: ph.phi4flash_forward(p, t, F32))(params, out[:, :-1]))
    np.testing.assert_array_equal(out[:, 19:],
                                  logits[:, 18:].argmax(-1))


def _paged(slots=3, blocks=40):
    return m.phi4flash_init_paged_cache(F32, slots, num_blocks=blocks,
                                        block_size=BS)


_PREFILL = jax.jit(
    lambda p, c, t, bt, pre, n, slot, state: m.phi4flash_paged_prefill(
        p, c, t, F32, row_bt=bt, prefix_len=pre, n_tail=n, slot=slot,
        state=state))
_STEP = jax.jit(lambda p, c, t: m.phi4flash_decode_step(p, c, t, F32))
ROW_BT = jnp.arange(1, 1 + 128 // BS, dtype=jnp.int32)


def _tail(toks, lo, hi, t_pad):
    """toks[lo:hi] right-aligned in `t_pad` columns."""
    out = np.zeros((1, t_pad), np.int32)
    out[0, t_pad - (hi - lo):] = toks[0, lo:hi]
    return jnp.asarray(out), lo, hi - lo


def _state(source=STATE_FROM_ZERO, entry=NO_SNAPSHOT, boundary=0):
    return jnp.asarray([source, entry, boundary], jnp.int32)


@pytest.mark.parametrize("n,t_pad", [(5, 16), (16, 16), (23, 32), (40, 48)])
def test_paged_prefill_then_decode_equal_the_full_forward(n, t_pad, params,
                                                          want):
    """Paged against the full forward, which the dense cache equals."""
    toks, logits = want
    lg, cache = _PREFILL(params, _paged(), _tail(toks, 0, n, t_pad)[0],
                         ROW_BT, 0, n, 1, _state())
    np.testing.assert_allclose(lg, logits[n - 1], atol=TOL)
    for k in range(n, min(n + 5, 48)):
        lg, cache = _STEP(params, cache, jnp.asarray([0, toks[0, k], 0]))
        np.testing.assert_allclose(lg[1], logits[k], atol=TOL)
    assert int(cache["pos"][0]) == 0            # an idle row stays one
    reach = dc.cache_reach(cache)
    assert reach["pool_bytes_per_token"] == 2 * 32 * 4       # ONE layer
    assert reach["window_bytes_per_slot"] == 3 * 8 * 2 * 32 * 4
    assert reach["full_reach_bytes_per_token"] == 4 * 2 * 32 * 4


def test_a_prompt_admitted_in_chunks_is_one_shot(params, want):
    """Three pieces of 16, 16 and 8: state, convolution rows and rings
    carry from piece to piece in the slot's own rows, and only the last
    piece's cross-decoder answers."""
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
    for name in STATE:
        np.testing.assert_allclose(cache[name], whole[name], atol=TOL)
    for name in ("k", "v"):         # but the null block, the pads' sink
        np.testing.assert_allclose(cache[name][:, 1:], whole[name][:, 1:],
                                   atol=TOL)


def test_a_prefix_hit_starts_from_its_snapshot(params, want):
    """A prompt leaves all four tensors after its block boundary (24
    tokens) in snapshot entry 1; another slot's prompt with those 24
    resident starts from it and reads the logits a cold prompt reads."""
    toks, logits = want
    _, cache = _PREFILL(params, _paged(), _tail(toks, 0, 29, 32)[0],
                        ROW_BT, 0, 29, 0, _state(entry=1, boundary=24))
    cold = _PREFILL(params, _paged(), _tail(toks, 0, 24, 32)[0], ROW_BT, 0,
                    24, 0, _state())[1]
    for name in STATE:
        axis = 2 if name == "conv" else 1
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
    # three slots and their snapshots: four Mamba layers' state and
    # windows, three window layers' rings
    assert dc.state_bytes(hit) == 2 * 3 * (
        4 * (16 * 128 * 4 + 3 * 128 * 4) + 3 * 8 * 2 * 32 * 4)


def test_an_idle_or_parked_row_keeps_its_state(params, want):
    toks, _ = want
    _, cache = _PREFILL(params, _paged(), _tail(toks, 0, 20, 32)[0],
                        ROW_BT, 0, 20, 1, _state())
    parked = dc.clear_row(cache, 1)
    after = _STEP(params, parked, jnp.asarray([3, 4, 5]))[1]
    for name in STATE:
        assert bool(jnp.all(after[name] == cache[name]))
    assert after["pos"].tolist() == [0, 0, 0]


def _walk(jaxpr, seen):
    """(name stack, equation) of every equation, inner programs too."""
    for eqn in jaxpr.eqns:
        seen.append((str(eqn.source_info.name_stack), eqn))
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk(sub, seen)
    return seen


def test_a_prefills_cross_decoder_sees_one_token_a_row(params, want):
    """From the traced program's shapes, not from timing: under the
    scopes ``attn_cross`` and ``gmu`` of a 48-column paged prefill no
    value has a 48, and every product has 8 rows (one token, as eight
    equal rows); the self-decoder's products have the 48.  The
    last-position logits are the reference's, which ran all twelve
    layers over all positions."""
    toks, _ = want
    tail = _tail(toks, 0, 40, 48)[0]
    jaxpr = jax.make_jaxpr(
        lambda p, c, t: m.phi4flash_paged_prefill(
            p, c, t, F32, row_bt=ROW_BT, prefix_len=0, n_tail=40, slot=1)
    )(params, _paged(), tail)
    seen = _walk(jaxpr.jaxpr, [])
    cross = [e for stack, e in seen
             if {"attn_cross", "gmu"} & set(stack.split("/"))]
    assert len(cross) > 40
    for e in cross:
        for v in e.outvars:
            assert 48 not in v.aval.shape, (e.primitive, v.aval.shape)
    dots = [e for e in cross if e.primitive.name == "dot_general"]
    # a pair's products: the GMU's two, W_q, the pool's scores and
    # values (two K/V pair-heads each), W_o
    assert len(dots) >= 6
    assert {e.outvars[0].aval.shape[0] for e in dots
            if "kv_pool" not in str(e.source_info.name_stack)} <= {8, 1}
    mlp_rows = {e.outvars[0].aval.shape[-2] for stack, e in seen
                if e.primitive.name == "dot_general" and "mlp" in stack}
    assert mlp_rows == {48, 8}          # the self-decoder's, the cross's
    lg, _ = _PREFILL(params, _paged(), tail, ROW_BT, 0, 40, 1, _state())
    ref = REFERENCE.logits(params, toks[:, :40], **STATED)
    np.testing.assert_allclose(lg, ref[0, 39], atol=TOL)


@pytest.mark.parametrize("fault", ["bf16_state", "no_lambda_term",
                                   "stale_memory", "no_window"])
def test_a_wrong_model_fails_the_tolerance(fault, params, want,
                                           monkeypatch):
    """What the tolerance is tight enough to see: the SSM state kept in
    bf16 between two programs, the differential term dropped, a Gated
    Memory Unit fed the LAST step's memory, a window layer whose
    prefill attends everything.  Each through prefill and decode
    against the forward."""
    toks, logits = want
    cfg = F32
    if fault == "bf16_state":
        cfg = ph.phi4flash_config("nano", dtype=jnp.float32,
                                  state_dtype=jnp.bfloat16)
    elif fault == "no_lambda_term":
        real = ph.plain_rmsnorm
        monkeypatch.setattr(
            ph, "diff_out", lambda o, p, lam_init, c: _no_lambda(
                o, p, lam_init, c, real))
    elif fault == "stale_memory":
        held = {}
        real_cd = ph.cross_decoder

        def stale(params_, x, mem, c, attend):
            last = held.get("m", jnp.zeros_like(mem))
            held["m"] = mem
            return real_cd(params_, x, last, c, attend)

        monkeypatch.setattr(m, "cross_decoder", stale)
    else:
        monkeypatch.setattr(m, "causal_mask",
                            lambda T, window=None: ph.causal_mask(T))
    # new functions, so that no compiled program of another test answers;
    # the stale memory is carried by the host between eager steps
    wrap = (lambda f: f) if fault == "stale_memory" else jax.jit
    prefill = wrap(lambda p, t: m.phi4flash_prefill(p, t, cfg))
    step = wrap(lambda p, c, t: m.phi4flash_decode_step(p, c, t, cfg))
    lg, cache = prefill(params, jnp.asarray(toks[:, :30]))
    worst = 0.0
    for k in range(30, 34):
        lg, cache = step(params, cache, jnp.asarray(toks[:, k]))
        worst = max(worst, float(jnp.max(jnp.abs(lg[0] - logits[k]))))
    assert worst > 20 * TOL, worst


def _no_lambda(o, p, lam_init, cfg, rmsnorm):
    """`diff_out` with ``lam = 0``: the first softmax alone."""
    o = o.astype(jnp.float32).reshape(*o.shape[:-2], cfg.n_head // 2, 2, -1)
    mixed = rmsnorm(o[..., 0, :], p["subln"].astype(jnp.float32),
                    cfg.ln_eps) * (1.0 - lam_init)
    mixed = mixed.reshape(*mixed.shape[:-2], cfg.d_model)
    return mixed @ p["wo"] + p["bo"]


# -- what the programs call their parts ---------------------------------------

EVERY = {"embed", "ln", "ssm", "ssm_state", "attn_window", "attn_full",
         "attn_cross", "gmu", "kv_pool", "mlp", "lm_head", "sample",
         "layer_scan"}


def _lowered(name, params):
    key = jax.random.PRNGKey(1)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)  # noqa: E731

    def pool_step(p, cache, toks, key):
        logits, cache = m.phi4flash_decode_step(p, cache, toks, F32)
        return sample_token(logits, key, 0.0, None), cache

    def prefill_sample(p, cache, toks, row_bt, key, state):
        logits, cache = m.phi4flash_paged_prefill(
            p, cache, toks, F32, row_bt=row_bt, prefix_len=0, n_tail=21,
            slot=0, state=state)
        return sample_token(logits[None], key, 0.0, None), cache

    if name == "decode_step":
        return jax.jit(pool_step).lower(params, _paged(2, 20), i32(2), key)
    return jax.jit(prefill_sample).lower(
        params, _paged(2, 20), i32(1, 32), i32(128 // BS), key, i32(3))


@pytest.mark.parametrize("program", ["decode_step", "paged_prefill"])
def test_the_new_scopes_hold_the_cross_decoder(program, params):
    assert {scopes.ATTN_CROSS, scopes.GMU} <= scopes.DEVICE_SCOPES
    assert not {scopes.ATTN_CROSS, scopes.GMU} & scopes.CONTAINER_SCOPES
    ops = _op_scopes(_lowered(program, params))
    found = collections.Counter(s for _, s in ops)
    assert set(found) - {None} == EVERY
    loose = [op for op, s in ops if s is None]
    assert len(loose) <= 0.10 * len(ops), collections.Counter(loose)
    heavy = {"stablehlo.dot_general", "stablehlo.exponential",
             "stablehlo.gather", "stablehlo.scatter"}
    assert not heavy & set(loose), collections.Counter(loose)
    # a softmax each for the three kinds of attention; the GMU's gate
    # and the Mamba layers' are logistic
    exps = collections.Counter(s for op, s in ops
                               if op == "stablehlo.exponential")
    assert exps[scopes.ATTN_CROSS] and exps[scopes.ATTN_FULL] \
        and exps[scopes.ATTN_WINDOW]
    dots = collections.Counter(s for op, s in ops
                               if op == "stablehlo.dot_general")
    assert dots[scopes.GMU] == 2 and dots[scopes.SSM] >= 4


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
    return build_llm_deployment("phi4flash", "nano", temperature=0.0,
                                config_overrides=_OVR, **kw)


_ORACLE = {}


def _oracle(prompt):
    """`generate`'s answer to `prompt`, left-padded to 40 columns so
    that one program answers every prompt."""
    if "fn" not in _ORACLE:
        cfg = ph.phi4flash_config("nano", **_OVR)
        weights = ph.phi4flash_init(jax.random.PRNGKey(0), cfg)
        generate = jax.jit(lambda p, t, n: m.phi4flash_generate(
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
    """A repeats: its second admission hits two blocks and the snapshot
    of all four tensors at their boundary (paged), and answers as the
    cold one; B shares 32 tokens with A and starts from the same
    snapshot."""
    prompts = [A, C, D, A, B]
    outs, stats, hits = _serve(_build(**kw), prompts)
    for prompt, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["requests"]["finished"] == 5
    if kw.get("kv_layout") != "dense":
        assert hits == [0, 0, 0, 2, 2]
        assert stats["recurrent"]["snapshot_hits"] == 2
        assert stats["recurrent"]["state_bytes"] == 2 * 3 * (
            4 * (16 * 128 * 4 + 3 * 128 * 4) + 3 * 8 * 2 * 32 * 4)
        # the pool and the rings, counted as Laguna's are: one layer at
        # full reach where four would keep every position
        reach = stats["kv_reach"]
        assert reach["waves"] > 0
        assert 0 < reach["reserved_share"] < 1


@pytest.mark.parametrize("kw,option", [
    ({"spec_decode": SpecConfig(draft="ngram", k=2)}, "spec_decode"),
    ({"kv_host_tier_bytes": 1 << 20}, "kv_host_tier_bytes"),
    ({"role": "prefill"}, "role='prefill'"),
    ({"mesh": object()}, "mesh")])
def test_what_cannot_carry_both_kinds_of_state_is_refused(kw, option):
    kind = families.cache_kind("phi4flash")
    assert kind == families.RECURRENT_WINDOWED == "kv+recurrent+window"
    assert kind in families.PER_SLOT_STATE and kind in families.CACHE_HOLDS
    with pytest.raises(ValueError) as e:
        _build(**kw)
    assert "family 'phi4flash' keeps a kv+recurrent+window cache" \
        in str(e.value)
    assert f"{option} cannot carry yet" in str(e.value)
