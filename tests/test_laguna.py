"""Family ``laguna``: window and full attention layers of different head
counts over a cache that reserves by layer type.

The programs (paged prefill, then decode through the pool and the
rings) are held to ``benchmark/reference/laguna.py``'s full forward, the
plain float32 reference that shares no code with them, LOGITS at a
stated tolerance; the engine on its normal path (continuous scheduler,
pager, snapshots) is held to the dense oracle; a wrong model fails the
tolerance; and what cannot carry the rings is refused at the options
check.  Sizes: the ``nano`` preset, both layer kinds at 6 / 8 query
heads over 2 K/V heads, window 8, so a context of 5-40 wraps the ring
several times.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode_common as dc
from ray_tpu.models import banded_attention, families, laguna
from ray_tpu.models import laguna_decode as m
from ray_tpu.models.laguna import laguna_config, laguna_init
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment
from tests.test_kimi_k2_serve import _serve

HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "laguna_reference", os.path.join(
        HERE, "..", "benchmark", "reference", "laguna.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

F32 = laguna_config("nano", dtype=jnp.float32)
BF16 = laguna_config("nano")
BS = 4
#: float32 programs against the float32 reference: rounding alone, at
#: logits of std 0.16 (measured here: under 4e-7, where a router fed
#: bf16 inputs reads 1.5e-5)
F32_TOL = 1e-5
#: bf16 programs (float32 weights) against it: the bf16 residual stream
#: and matmul inputs; measured here over these cases: under 0.012,
#: where dropping the gate or the window mask moves a logit by 0.07 and
#: more
BF16_TOL = 0.03


def _kwargs(cfg):
    return dict(
        vocab_size=cfg.vocab_size, layer_types=cfg.layer_types,
        n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim, window=cfg.window,
        top_k=cfg.top_k, route_scale=cfg.route_scale,
        full_rotary_dim=cfg.full_rotary_dim,
        full_rope_theta=cfg.full_rope_theta, rope_factor=cfg.rope_factor,
        rope_orig_max=cfg.rope_orig_max, beta_fast=cfg.beta_fast,
        beta_slow=cfg.beta_slow, attention_factor=cfg.attention_factor,
        window_rope_theta=cfg.window_rope_theta, eps=cfg.rms_eps)


@pytest.fixture(scope="module")
def params():
    return laguna_init(jax.random.PRNGKey(0), F32)


def _tokens(seed, n):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, 512), np.int32)


SEQ = _tokens(1, 40)
OTHER = _tokens(2, 40)


@pytest.fixture(scope="module")
def want(params):
    """The reference's logits for SEQ and OTHER, every position."""
    return {name: np.asarray(reference.logits(
        params, jnp.asarray(seq[None]), **_kwargs(F32)))[0]
        for name, seq in (("seq", SEQ), ("other", OTHER))}


def _paged_cache(cfg, slots=3):
    return m.laguna_init_paged_cache(
        cfg, slots, num_blocks=1 + slots * cfg.max_seq // BS,
        block_size=BS)


def _row_bt(cfg, slot):
    nb = cfg.max_seq // BS
    return jnp.asarray(1 + slot * nb + np.arange(nb), jnp.int32)


def _jitted(cfg):
    """(paged prefill, decode step) of `cfg`, jitted anew: a trace made
    while a test steers the model must not outlive it."""
    def prefill(params, cache, tail, row_bt, prefix_len, n_tail, slot,
                state):
        return m.laguna_paged_prefill(
            params, cache, tail, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot, state=state)

    return jax.jit(prefill), jax.jit(
        lambda params, cache, toks: m.laguna_decode_step(params, cache,
                                                         toks, cfg))


_PROGRAMS = {}


def _programs(cfg):
    if cfg not in _PROGRAMS:
        _PROGRAMS[cfg] = _jitted(cfg)
    return _PROGRAMS[cfg]


def _prefill(params, cache, cfg, seq, lo, hi, slot, t_pad, state,
             row_bt=None, programs=None):
    tail = np.zeros((1, t_pad), np.int32)
    tail[0, t_pad - (hi - lo):] = seq[lo:hi]
    return (programs or _programs(cfg))[0](
        params, cache, jnp.asarray(tail),
        _row_bt(cfg, slot) if row_bt is None else row_bt, np.int32(lo),
        np.int32(hi - lo), np.int32(slot), jnp.asarray(state, jnp.int32))


def _decode(params, cache, cfg, seq, lo, hi, slot, want, slots=3,
            programs=None):
    """Decode seq[lo:hi] through `slot`; the largest logit error."""
    worst = 0.0
    step = (programs or _programs(cfg))[1]
    for t in range(lo, hi):
        toks = np.zeros((slots,), np.int32)
        toks[slot] = seq[t]
        logits, cache = step(params, cache, jnp.asarray(toks))
        worst = max(worst, float(np.max(np.abs(
            np.asarray(logits[slot, :cfg.vocab_size]) - want[t]))))
    return worst, cache


@pytest.mark.parametrize("cfg,tol", [(F32, F32_TOL), (BF16, BF16_TOL)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("n,t_pad", [(5, 16), (8, 16), (16, 16), (23, 32),
                                     (32, 32)],
                         ids=["under_the_window", "the_window", "two_windows",
                              "ragged", "block_boundary"])
def test_paged_prefill_then_decode_equal_the_reference(cfg, tol, n, t_pad,
                                                       params, want):
    """A prompt shorter than the window, one that fills it, ones that
    wrap it and end on a block boundary or off it; then decode to 40,
    the ring wrapping up to four times more."""
    logits, cache = _prefill(params, _paged_cache(cfg), cfg, SEQ, 0, n, 1,
                             t_pad, [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0])
    err = float(np.max(np.abs(np.asarray(logits[:cfg.vocab_size])
                              - want["seq"][n - 1])))
    worst, cache = _decode(params, cache, cfg, SEQ, n, 40, 1, want["seq"])
    assert max(err, worst) < tol
    assert int(cache["pos"][1]) == 40 and int(cache["pos"][0]) == 0


def test_a_prefill_in_chunks_carries_the_ring(params, want):
    """Three chunks, each from the slot's own rings (STATE_FROM_SLOT),
    the middle one longer than the window."""
    cache, err = _paged_cache(F32), 0.0
    for lo, hi in ((0, 8), (8, 20), (20, 23)):
        source = dc.STATE_FROM_ZERO if lo == 0 else dc.STATE_FROM_SLOT
        logits, cache = _prefill(params, cache, F32, SEQ, lo, hi, 2, 16,
                                 [source, dc.NO_SNAPSHOT, 0])
        err = max(err, float(np.max(np.abs(
            np.asarray(logits[:F32.vocab_size]) - want["seq"][hi - 1]))))
    worst, _ = _decode(params, cache, F32, SEQ, 23, 40, 2, want["seq"])
    assert max(err, worst) < F32_TOL


def test_a_prefix_hit_restores_the_window_from_its_snapshot(params, want):
    """The harness's ``repeat_hit``: a first prefill leaves the rings
    after its deepest block boundary (20 tokens) in a snapshot entry;
    another slot then prefills only the tail behind those 20, from the
    snapshot, over the first's pool blocks, and answers as the
    reference does; so does a third that took the snapshot but whose
    slot held another sequence's longer past."""
    cache = _paged_cache(F32)
    _, cache = _prefill(params, cache, F32, SEQ, 0, 23, 0, 32,
                        [dc.STATE_FROM_ZERO, 1, 20])
    # slot 2 decodes something else meanwhile: its rings are stale
    _, cache = _prefill(params, cache, F32, OTHER, 0, 32, 2, 32,
                        [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0])
    _, cache = _decode(params, cache, F32, OTHER, 32, 40, 2, want["other"])
    for slot in (1, 2):
        # the hit: the first 20 tokens' blocks are slot 0's
        bt = _row_bt(F32, slot).at[:5].set(_row_bt(F32, 0)[:5])
        logits, cache = _prefill(params, cache, F32, SEQ, 20, 23, slot, 16,
                                 [1, dc.NO_SNAPSHOT, 0], row_bt=bt)
        err = float(np.max(np.abs(np.asarray(logits[:F32.vocab_size])
                                  - want["seq"][22])))
        worst, cache = _decode(params, cache, F32, SEQ, 23, 40, slot,
                               want["seq"])
        assert max(err, worst) < F32_TOL, slot


def test_a_slot_reused_by_a_shorter_request_attends_no_stale_row(params,
                                                                 want):
    """A longer request fills a slot's rings; a prompt shorter than the
    window then takes the slot: the rows it has not written hold the
    other's keys and must not be attended."""
    cache = _paged_cache(F32)
    _, cache = _prefill(params, cache, F32, OTHER, 0, 32, 1, 32,
                        [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0])
    _, cache = _decode(params, cache, F32, OTHER, 32, 40, 1, want["other"])
    cache = dc.clear_row(cache, 1)
    logits, cache = _prefill(params, cache, F32, SEQ, 0, 3, 1, 16,
                             [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0])
    err = float(np.max(np.abs(np.asarray(logits[:F32.vocab_size])
                              - want["seq"][2])))
    worst, _ = _decode(params, cache, F32, SEQ, 3, 12, 1, want["seq"])
    assert max(err, worst) < F32_TOL


def test_an_idle_or_parked_row_keeps_its_rings(params):
    """A row with ``pos == 0`` (parked between two chunks) is left as
    it is by a decode step: window rows and snapshot alike."""
    cache = _paged_cache(F32)
    _, cache = _prefill(params, cache, F32, SEQ, 0, 12, 1, 16,
                        [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0])
    parked = dc.clear_row(cache, 1)
    before = {n: np.asarray(parked[n]) for n in ("wk", "wv", "snap_wk")}
    _, after = _programs(F32)[1](params, parked,
                                 jnp.zeros((3,), jnp.int32))
    for name, held in before.items():
        np.testing.assert_array_equal(np.asarray(after[name]), held)
    assert np.asarray(after["pos"]).tolist() == [0, 0, 0]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_generate_equals_the_full_forward(layout, params):
    """The shared loop over the dense prefill and the decode step, both
    layouts, ragged left-padded rows: every new token is the full
    forward's argmax."""
    prompt = np.stack([SEQ[:12], OTHER[:12]])
    prompt[1, :5] = 0
    out = np.asarray(jax.jit(lambda p, t, n: m.laguna_generate(
        p, t, F32, max_new_tokens=14, temperature=0.0, lengths=n,
        kv_layout=layout, kv_block_size=BS))(
        params, jnp.asarray(prompt), jnp.asarray([12, 7])))
    for row, start in ((0, 0), (1, 5)):
        seq = out[row, start:]
        full = np.asarray(laguna.laguna_forward(
            params, jnp.asarray(seq[None]), F32))[0, :, :F32.vocab_size]
        np.testing.assert_array_equal(
            seq[12 - start:], np.argmax(full[12 - start - 1:-1], axis=-1))


# -- a wrong model fails the tolerance ---------------------------------------

def _wrong_error(monkeypatch, params, want, cfg, patch):
    """The largest logit error of the steered model over two cases: a
    prompt of 23 tokens decoded to 40, and a prompt of 3 in a slot that
    held another sequence's longer past."""
    stale = _paged_cache(cfg)
    _, stale = _prefill(params, stale, cfg, OTHER, 0, 32, 1, 32,
                        [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0])
    stale = dc.clear_row(stale, 1)
    patch(monkeypatch)
    wrong = _jitted(cfg)
    worst = 0.0
    for cache, n, end in ((_paged_cache(cfg), 23, 40), (stale, 3, 12)):
        logits, cache = _prefill(
            params, cache, cfg, SEQ, 0, n, 1, 32,
            [dc.STATE_FROM_ZERO, dc.NO_SNAPSHOT, 0], programs=wrong)
        err = float(np.max(np.abs(np.asarray(logits[:cfg.vocab_size])
                                  - want["seq"][n - 1])))
        late, _ = _decode(params, cache, cfg, SEQ, n, end, 1, want["seq"],
                          programs=wrong)
        worst = max(worst, err, late)
    return worst


def _stale_rows_attended(mp):
    mp.setattr(banded_attention, "_ring_mask",
               lambda pos, start, window: jnp.ones((pos.shape[0], window),
                                                   bool))


def _no_window_in_prefill(mp):
    real = m.attend_banded
    mp.setattr(m, "attend_banded",
               lambda q, k, v, first, last, cfg, scope: real(
                   q, k, v, jnp.zeros_like(first), last, cfg, scope))


def _no_gate(mp):
    real = laguna.attn_out
    mp.setattr(laguna, "attn_out",
               lambda o, gate, p, cfg: real(o, jnp.ones_like(gate), p, cfg))


def _sigmoid_weights(mp):
    real = laguna.LagunaConfig.experts.fget
    mp.setattr(laguna.LagunaConfig, "experts", property(
        lambda self: dataclasses.replace(real(self), scoring="sigmoid")))


def _bf16_router(mp):
    from ray_tpu.models import experts as ex

    real = ex.route
    mp.setattr(ex, "route", lambda router, x32, cfg: real(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16).astype(a.dtype),
                     router),
        x32.astype(jnp.bfloat16).astype(jnp.float32), cfg))


@pytest.mark.parametrize("patch,cfg,tol", [
    (_stale_rows_attended, F32, F32_TOL),
    (_no_window_in_prefill, F32, F32_TOL), (_no_gate, F32, F32_TOL),
    (_sigmoid_weights, F32, F32_TOL), (_bf16_router, F32, F32_TOL),
    (_stale_rows_attended, BF16, BF16_TOL),
    (_no_window_in_prefill, BF16, BF16_TOL), (_no_gate, BF16, BF16_TOL)],
    ids=["stale_ring_rows_attended", "window_dropped_in_prefill",
         "gate_left_out", "sigmoid_weights", "bf16_router",
         "stale_ring_rows_attended_bf16", "window_dropped_in_prefill_bf16",
         "gate_left_out_bf16"])
def test_a_wrong_model_fails_the_tolerance(patch, cfg, tol, params, want,
                                           monkeypatch):
    """Each of these is a model the cell must not call correct: a
    decode step that attends stale ring rows, a prefill whose window
    layers attend the whole causal triangle, an attention output left
    ungated, sigmoid scores for the 8 weights, a router computed from
    bf16 inputs (float32 programs see it; under bf16 programs the
    residual stream's own rounding is as large, which is why the
    router stays float32 and the cell's tolerance is the family's
    own)."""
    assert _wrong_error(monkeypatch, params, want, cfg, patch) > tol


# -- the cache, by layer type -------------------------------------------------

def test_the_cache_reserves_by_layer_type():
    """The pool holds the full layers alone; a window layer holds
    `window` rows a slot whatever ``max_seq`` is; `cache_reach` says
    so in bytes."""
    for max_seq in (128, 256):
        cfg = laguna_config("nano", max_seq=max_seq)
        cache = m.laguna_init_paged_cache(cfg, 3, num_blocks=40,
                                          block_size=BS)
        assert cache["k"].shape == (2, 40, BS, 32)         # 2 full layers
        assert cache["wk"].shape == cache["snap_wv"].shape == (1, 3, 8, 32)
        assert cache["block_tables"].shape == (3, max_seq // BS)
        reach = dc.cache_reach(cache)
        assert reach == {"pool_bytes_per_token": 2 * 2 * 32 * 2,
                         "window_bytes_per_slot": 1 * 2 * 8 * 32 * 2,
                         "window_rows": 8,
                         "full_reach_bytes_per_token": 3 * 2 * 32 * 2}
        assert dc.block_bytes(cache) == BS * 2 * 2 * 32 * 2
        assert dc.state_bytes(cache) == 2 * 3 * 2 * 8 * 32 * 2


def test_a_decode_step_reads_a_window_whatever_the_context():
    """The decode program's bytes accessed do not grow with the context
    in its window layers: compiled at two ``max_seq``, what differs is
    the full layers' share (the pool and the tables)."""
    def accessed(max_seq):
        cfg = laguna_config("nano", max_seq=max_seq, n_head=8, layer_types=(
            "window", "window", "window"), heads_per_layer=(8, 8, 8))
        params = jax.eval_shape(lambda: laguna_init(jax.random.PRNGKey(0),
                                                    cfg))
        cache = jax.eval_shape(lambda: m.laguna_init_paged_cache(
            cfg, 3, num_blocks=8, block_size=BS))
        cost = jax.jit(lambda p, c, t: m.laguna_decode_step(
            p, c, t, cfg)).lower(params, cache, jax.ShapeDtypeStruct(
                (3,), jnp.int32)).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        return cost["bytes accessed"]

    small, large = accessed(128), accessed(1024)
    # only the block tables grew: (1024 - 128) / 4 entries x 3 rows x 4 B,
    # read and written back
    assert 0 <= large - small <= 4 * 3 * 4 * (1024 - 128) // BS


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
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    return build_llm_deployment("laguna", "nano", temperature=0.0,
                                config_overrides=_OVR, **kw)


_ORACLE = {}


def _oracle(prompt):
    key = prompt.tobytes()
    if key not in _ORACLE:
        cfg = laguna_config("nano", **_OVR)
        weights = laguna_init(jax.random.PRNGKey(0), cfg)
        _ORACLE[key] = np.asarray(jax.jit(
            lambda p, t: m.laguna_generate(
                p, t, cfg, max_new_tokens=MAX_NEW, temperature=0.0))(
            weights, jnp.asarray(prompt[None])))[0]
    return _ORACLE[key]


@pytest.mark.parametrize("kw", [
    {}, {"prefill_bucket": 64}, {"prefill_chunk_tokens": 16},
    {"kv_layout": "dense"}, {"scheduler": "batch", "kv_layout": "dense"}],
    ids=["paged", "bucket64", "chunked", "dense", "batch"])
def test_the_engine_answers_as_the_dense_oracle(kw):
    outs, stats, _ = _serve(_build(**kw), [A, C, D, B])
    for prompt, out in zip([A, C, D, B], outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["requests"]["finished"] == 4


def test_requests_together_answer_as_alone():
    outs, _, _ = _serve(_build(), [A, C, D, B], together=True)
    for prompt, out in zip([A, C, D, B], outs):
        np.testing.assert_array_equal(out, _oracle(prompt))


@pytest.mark.parametrize("kw", [{}, {"prefill_chunk_tokens": 16}],
                         ids=["whole", "chunked"])
def test_a_repeated_prompt_hits_its_prefix_and_answers_as_cold(kw):
    """The harness's ``repeat_hit``: 40 tokens, two blocks of the full
    layers' K/V resident and the rings' snapshot at their boundary, 8
    tokens prefilled; the answer is the cold one.  B shares 32 tokens
    with A and starts from the same snapshot."""
    outs, stats, hits = _serve(_build(**kw), [A, A, B])
    np.testing.assert_array_equal(outs[0], _oracle(A))
    np.testing.assert_array_equal(outs[1], outs[0])
    np.testing.assert_array_equal(outs[2], _oracle(B))
    assert hits == [0, 2, 2]
    assert stats["kv_cache"]["prefix_block_hits"] == 4
    assert stats["recurrent"]["snapshot_hits"] == 2
    assert stats["recurrent"]["state_bytes"] > 0


def test_the_bytes_reserved_by_layer_type_land_with_the_tokens():
    """One request of 40 + 6 tokens: 3 blocks of 16 in the pool of the
    2 full layers, one slot's ring of the window layer; every layer at
    full reach would hold 3 layers' rows of 48 tokens."""
    _, stats, _ = _serve(_build(), [A])
    reach = stats["kv_reach"]
    row = 2 * 32 * 4                    # K and V, 2 x 16 lanes, float32
    assert reach["waves"] == MAX_NEW - 1
    assert reach["pool_bytes"] == 48 * 2 * row
    assert reach["window_bytes"] == 8 * row
    assert reach["full_reach_bytes"] == 48 * 3 * row
    assert reach["reserved_share"] == round((96 + 8) / 144, 4)
    experts = stats["experts"]
    assert set(experts) == {"decode", "prefill"}
    assert experts["decode"]["held"] == experts["decode"]["of"] == 16
    # one row a wave: every touched expert's rows fill one row tile; a
    # prefill of 40 tokens over 16 experts overflows some experts' tiles
    assert experts["decode"]["row_tiles_per_touched"] == 1.0
    assert 1.0 <= experts["prefill"]["row_tiles_per_touched"] < 3.0
    from ray_tpu.util.metrics import _registry

    assert _registry.snapshot()["serve_kv_reach_full_bytes_total"]["values"]


def test_a_family_without_windows_reserves_everything_at_full_reach():
    from ray_tpu.models.gpt2_decode import init_paged_cache
    from ray_tpu.models.gpt2 import gpt2_config

    cache = init_paged_cache(gpt2_config("nano"), 2, num_blocks=8,
                             block_size=16)
    reach = dc.cache_reach(cache)
    assert reach["window_bytes_per_slot"] == reach["window_rows"] == 0
    assert reach["pool_bytes_per_token"] \
        == reach["full_reach_bytes_per_token"] \
        == dc.block_bytes(cache) // 16


@pytest.mark.parametrize("kw,option", [
    ({"spec_decode": SpecConfig(draft="ngram", k=2)}, "spec_decode"),
    ({"kv_host_tier_bytes": 1 << 20}, "kv_host_tier_bytes"),
    ({"role": "prefill"}, "role='prefill'"),
    ({"mesh": object()}, "mesh")])
def test_what_cannot_carry_the_rings_is_refused(kw, option):
    assert families.cache_kind("laguna") == families.WINDOWED
    with pytest.raises(ValueError) as e:
        _build(**kw)
    assert "family 'laguna' keeps a kv+window cache" in str(e.value)
    assert f"{option} cannot carry yet" in str(e.value)
