"""Family ``jamba`` (models/jamba.py, jamba_decode.py; served by
serve/llm.py): Mamba layers with per-slot recurrent state beside the
paged K/V pool of the attention layers.

Everything at a small size on the CPU, seeded weights, float32 unless a
case says otherwise.  The yardstick is the benchmark's plain reference
(``benchmark/reference/jamba.py``: float32, a time-step scan, no
cache), on LOGITS.  Tolerances, each with its reason:

* ``F32_ATOL`` 5e-6: program and reference both compute in float32 on
  the CPU; they differ in how the recurrence's ``exp`` and outer
  product are fused (the program's chunked scan) and in the order of
  the matmuls' sums.  Logits are O(1); measured 0 to 2.4e-7 (one ulp
  of a logit near 2), so twenty times the largest reading.
* ``BF16_STATE_MIN`` 3e-5: the SSM state kept in bfloat16 between
  decode steps, everything else float32, moves the logits by 5.1e-5 to
  7.4e-5 over 35 decoded tokens at this size (three seeds): two hundred
  times the float32 reading and ten times ``F32_ATOL``, so the float32
  tolerance tells the two apart.
* the engine against ``jamba_generate`` (the dense parity oracle):
  bit-equal tokens, as for every family (tests/test_serve_paged.py).
"""

import asyncio
import importlib.util
import os
import warnings

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.jamba import (jamba_config, jamba_forward,  # noqa: E402
                                  jamba_init, jamba_loss,
                                  jamba_param_count)
from ray_tpu.models.decode_common import (NO_SNAPSHOT,  # noqa: E402
                                          STATE_FROM_ZERO)
from ray_tpu.models.jamba_decode import (jamba_decode_step,  # noqa: E402
                                         jamba_generate,
                                         jamba_init_paged_cache,
                                         jamba_paged_prefill,
                                         jamba_prefill)
from ray_tpu.serve.kv_pager import BlockPager, StateSnapshots  # noqa: E402
from ray_tpu.serve.llm import (SpecConfig,  # noqa: E402
                               build_llm_deployment)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32_ATOL = 5e-6
BF16_STATE_MIN = 3e-5
MAX_NEW = 6
_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_jamba_{kind}", os.path.join(ROOT, "benchmark", kind,
                                       name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def reference():
    return _load("reference", "jamba")


@pytest.fixture(scope="module")
def family():
    return _load("families", "jamba")


@pytest.fixture(scope="module")
def tiny():
    cfg = jamba_config("nano", **_OVR)
    return cfg, jamba_init(jax.random.PRNGKey(0), cfg)


def _ref_logits(reference, params, cfg, tokens):
    return np.asarray(reference.logits(
        params, jnp.asarray(tokens), vocab_size=cfg.vocab_size,
        attn_period=cfg.attn_period, attn_offset=cfg.attn_offset,
        eps=cfg.rms_eps))


def _tokens(seed, *shape):
    return np.random.RandomState(seed).randint(2, 500, shape).astype(
        np.int32)


# -- the model against the reference ----------------------------------------

@pytest.mark.parametrize("period,offset,layers", [(4, 2, 4), (4, 0, 8),
                                                  (2, 1, 4)])
def test_forward_matches_the_reference(reference, period, offset, layers):
    """Full-sequence logits, for three layer patterns (attention in the
    middle of a period, first in it, and every other layer)."""
    cfg = jamba_config("nano", n_layer=layers, attn_period=period,
                       attn_offset=offset, **_OVR)
    params = jamba_init(jax.random.PRNGKey(1), cfg)
    toks = _tokens(1, 2, 40)
    got = jamba_forward(params, jnp.asarray(toks), cfg)
    np.testing.assert_allclose(
        np.asarray(got)[..., :cfg.vocab_size],
        _ref_logits(reference, params, cfg, toks), atol=F32_ATOL)


def test_loss_matches_the_reference(reference, tiny):
    cfg, params = tiny
    toks = _tokens(2, 2, 33)
    want = reference.loss(params, jnp.asarray(toks),
                          vocab_size=cfg.vocab_size,
                          attn_period=cfg.attn_period,
                          attn_offset=cfg.attn_offset, eps=cfg.rms_eps)
    got = jamba_loss(params, {"tokens": jnp.asarray(toks)}, cfg)
    assert abs(float(got) - float(want)) < 1e-5


def _decode_logits(params, cfg, prompt, steps, layout):
    """Logits of prefill-then-decode, teacher-forced on `steps`."""
    logits, cache = jamba_prefill(params, jnp.asarray(prompt), cfg)
    if layout == "paged":
        from ray_tpu.models.decode_common import dense_to_paged
        cache = dense_to_paged(cache, 16)
    out = [logits]
    for t in range(steps.shape[1]):
        logits, cache = jamba_decode_step(params, cache,
                                          jnp.asarray(steps[:, t]), cfg)
        out.append(logits)
    return np.stack([np.asarray(x) for x in out], axis=1)


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_then_decode_matches_the_reference(reference, tiny,
                                                   layout):
    """The cache path (one prefill, then a token at a time through the
    recurrent state and the K/V cache) gives the logits of the
    reference's full forward pass at every generated position."""
    cfg, params = tiny
    toks = _tokens(3, 2, 30)
    got = _decode_logits(params, cfg, toks[:, :24], toks[:, 24:29],
                         layout)
    want = _ref_logits(reference, params, cfg, toks[:, :29])[:, 23:29]
    np.testing.assert_allclose(got[..., :cfg.vocab_size], want,
                               atol=F32_ATOL)


def test_a_bf16_ssm_state_fails_the_float32_tolerance(reference):
    """The nearest precision below the stated one, for the state: kept
    in bfloat16 between decode steps it misses the reference by far more
    than ``F32_ATOL``, so the comparison would catch it."""
    cfg = jamba_config("nano", state_dtype=jnp.bfloat16, **_OVR)
    params = jamba_init(jax.random.PRNGKey(0), cfg)
    toks = _tokens(3, 2, 60)
    got = _decode_logits(params, cfg, toks[:, :24], toks[:, 24:59],
                         "dense")
    want = _ref_logits(reference, params, cfg, toks[:, :59])[:, 23:59]
    worst = np.abs(got[..., :cfg.vocab_size] - want).max()
    assert worst > BF16_STATE_MIN > F32_ATOL


def test_dense_generate_is_greedy_under_the_reference(reference, tiny):
    cfg, params = tiny
    prompt = _tokens(4, 2, 24)
    out = np.asarray(jamba_generate(params, jnp.asarray(prompt), cfg,
                                    max_new_tokens=8, temperature=0.0))
    want = _ref_logits(reference, params, cfg, out)[:, 23:-1]
    np.testing.assert_array_equal(out[:, 24:], want.argmax(-1))


def test_ragged_rows_decode_as_they_would_alone(tiny):
    """A left-padded row steps over its pads: window and state move on
    real columns only."""
    cfg, params = tiny
    prompt = _tokens(5, 2, 24)
    prompt[1, :7] = 0
    both = jamba_generate(params, jnp.asarray(prompt), cfg,
                          max_new_tokens=8, temperature=0.0,
                          lengths=jnp.asarray([24, 17]))
    alone = jamba_generate(params, jnp.asarray(prompt[1:, 7:]), cfg,
                           max_new_tokens=8, temperature=0.0)
    np.testing.assert_array_equal(np.asarray(both)[1, 7:],
                                  np.asarray(alone)[0])


def _paged_prefill(params, cfg, prompt, bucket, slot=1, slots=3):
    """One prompt through `jamba_paged_prefill` at a padded length."""
    n = len(prompt)
    cache = jamba_init_paged_cache(cfg, slots, num_blocks=1 + 2 * 8,
                                   block_size=16)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, bucket - n:] = prompt
    row_bt = np.zeros((cfg.max_seq // 16,), np.int32)
    row_bt[:4] = [1, 2, 3, 4]
    state = jnp.asarray([STATE_FROM_ZERO, 0, (n - 1) // 16 * 16],
                        jnp.int32)
    return jamba_paged_prefill(
        params, cache, jnp.asarray(toks), cfg,
        row_bt=jnp.asarray(row_bt), prefix_len=jnp.int32(0),
        n_tail=jnp.int32(n), slot=jnp.int32(slot), state=state)


@pytest.mark.parametrize("pads", [1, 5, 27])
def test_pads_leave_the_recurrence_bit_equal(pads):
    """The part of "a prompt gives the same state at every bucket size"
    that is the program's own: `ssm_scan` over a sequence with `pads`
    identity columns (``dt = 0``) in front gives BIT-equal outputs on
    the real columns, final state and captured state, although the
    chunks' edges fall elsewhere.  (What a bucket also changes is the
    row count of the projections' matmuls, which is the backend's: the
    next test.)"""
    from ray_tpu.models.mamba import ssm_scan

    rng = np.random.RandomState(pads)
    T, di, N = 21, 32, 4
    dt = jnp.asarray(rng.uniform(0.01, 1.0, (1, T, di)), jnp.float32)
    x = jnp.asarray(rng.randn(1, T, di), jnp.float32)
    Bm = jnp.asarray(rng.randn(1, T, N), jnp.float32)
    Cm = jnp.asarray(rng.randn(1, T, N), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 4.0, (N, di)), jnp.float32)
    s0 = jnp.asarray(rng.randn(1, N, di), jnp.float32)
    front = lambda a: jnp.pad(a, ((0, 0), (pads, 0), (0, 0)))  # noqa: E731
    y, s, snap = ssm_scan(dt, x, A, Bm, Cm, s0, 8, capture=jnp.int32(12))
    yp, sp, snapp = ssm_scan(front(dt), front(x), A, front(Bm), front(Cm),
                             s0, 8, capture=jnp.int32(12 + pads))
    np.testing.assert_array_equal(np.asarray(yp)[:, pads:], np.asarray(y))
    np.testing.assert_array_equal(np.asarray(sp), np.asarray(s))
    np.testing.assert_array_equal(np.asarray(snapp), np.asarray(snap))
    # and the captured state is the state after column 12
    _, s12, _ = ssm_scan(dt[:, :13], x[:, :13], A, Bm[:, :13], Cm[:, :13],
                         s0, 8)
    np.testing.assert_array_equal(np.asarray(snap), np.asarray(s12))


@pytest.mark.parametrize("field", ["logits", "ssm", "conv", "snap_ssm",
                                   "snap_conv"])
def test_a_prompt_gives_the_same_state_at_two_bucket_sizes(tiny, field):
    """The same prompt at bucket 48 and at bucket 64: the same logits,
    window and state in its slot, and snapshot.  To 2e-6, not to the
    bit: the pads move nothing (the test above), but a CPU matmul sums
    in another order for 64 rows than for 48 (measured 2.4e-7 on logits
    near 0.2); a pad that walked the state would show as 1e-2."""
    cfg, params = tiny
    prompt = _tokens(6, 37)
    a_logits, a = _paged_prefill(params, cfg, prompt, 48)
    b_logits, b = _paged_prefill(params, cfg, prompt, 64)
    got = (a_logits, b_logits) if field == "logits" \
        else (a[field], b[field])
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(got[1]),
                               atol=2e-6, rtol=0)
    assert float(jnp.abs(got[0]).max()) > 1e-3
    if field == "ssm":      # and only its own slot's rows were written
        assert float(jnp.abs(a["ssm"][:, 0]).max()) == 0
        assert float(jnp.abs(a["ssm"][:, 2]).max()) == 0
    if field == "snap_ssm":  # entry 0, the state after 32 tokens
        _, c = _paged_prefill(params, cfg, prompt[:32], 32)
        np.testing.assert_allclose(np.asarray(a["snap_ssm"][:, 0]),
                                   np.asarray(c["ssm"][:, 1]), atol=2e-6,
                                   rtol=0)


def test_a_decode_step_leaves_rows_without_a_sequence_alone(tiny):
    """Rows with ``pos == 0`` (empty, or parked between two chunks of a
    prompt) keep window and state; the active row moves."""
    cfg, params = tiny
    _, cache = _paged_prefill(params, cfg, _tokens(7, 20), 32)
    parked = jax.tree.map(lambda x: x, cache)
    parked["ssm"] = cache["ssm"].at[:, 2].set(0.5)
    parked["conv"] = cache["conv"].at[:, :, 2].set(0.25)
    _, after = jamba_decode_step(params, parked,
                                 jnp.asarray([3, 4, 5], jnp.int32), cfg)
    np.testing.assert_array_equal(np.asarray(after["ssm"][:, 2]), 0.5)
    np.testing.assert_array_equal(np.asarray(after["conv"][:, :, 2]),
                                  0.25)
    assert float(jnp.abs(after["ssm"][:, 1]
                         - parked["ssm"][:, 1]).max()) > 0
    assert list(np.asarray(after["pos"])) == [1, 21, 1]


# -- the engine ---------------------------------------------------------------

def _build(**kw):
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    return build_llm_deployment("jamba", "nano", temperature=0.0,
                                config_overrides=_OVR, **kw)


def _serve(dep, prompts, together=False):
    async def main():
        inst = dep.func_or_class()
        try:
            if together:
                outs = await asyncio.gather(*[inst(p) for p in prompts])
            else:
                outs = [await inst(p) for p in prompts]
            hits = [r["kv_reserve"][3] if r.get("kv_reserve") else 0
                    for r in inst.trace_records()]
            return outs, inst.engine_stats(), hits, inst
        finally:
            if hasattr(inst, "_engine_task"):   # the batch scheduler
                inst.shutdown_engine()          # has no engine task

    return asyncio.run(main())


_ORACLE = {}


def _oracle(prompt):
    key = prompt.tobytes()
    if key not in _ORACLE:
        cfg = jamba_config("nano", **_OVR)
        params = jamba_init(jax.random.PRNGKey(0), cfg)
        _ORACLE[key] = np.asarray(jamba_generate(
            params, jnp.asarray(prompt[None]), cfg,
            max_new_tokens=MAX_NEW, temperature=0.0))[0]
    return _ORACLE[key]


A = _tokens(11, 40)
B = np.concatenate([A[:32], _tokens(12, 5)])
C = _tokens(13, 21)


@pytest.mark.parametrize("kw", [
    {}, {"prefill_bucket": 64}, {"prefill_chunk_tokens": 16},
    {"kv_layout": "dense"}, {"scheduler": "batch", "kv_layout": "dense"}],
    ids=["paged", "bucket64", "chunked", "dense", "batch"])
def test_the_engine_answers_as_the_dense_oracle(kw):
    """Through the same engine, pager and jitted programs as GPT-2:
    every answer is the dense solo greedy continuation, whatever the
    bucket, with streamed (chunked) prefill, and on the dense layout."""
    outs, stats, _, _ = _serve(_build(**kw), [A, C, B])
    for prompt, out in zip([A, C, B], outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["requests"]["finished"] == 3


def test_a_greedy_wave_draws_no_key():
    """Every decoding row greedy: the wave's sampler is an argmax, so
    the engine splits its PRNG key at admissions only (two here), not
    once a wave (the eager split was 1 ms of idle device a step on the
    chip; PERF.md, PR 28)."""
    _, stats, _, _ = _serve(_build(), [A, C])
    phases = stats["phases"]
    assert phases["rng_split"][0] == 2
    assert phases["decode_dispatch"][0] >= 2 * (MAX_NEW - 1)


def test_a_repeated_prompt_hits_and_answers_as_cold():
    """The harness's ``repeat_hit``: the second send of a prompt starts
    from the snapshot at its deepest block boundary (40 tokens: two
    blocks of 16 skipped, 8 prefilled) and answers what the first did.
    `hit_blocks` counts what was really skipped."""
    outs, stats, hits, _ = _serve(_build(), [A, A])
    np.testing.assert_array_equal(outs[0], _oracle(A))
    np.testing.assert_array_equal(outs[1], outs[0])
    assert hits == [0, 2]
    rec = stats["recurrent"]
    assert rec["snapshot_hits"] == 1 and rec["snapshot_misses"] == 0
    assert rec["snapshots_resident"] == 1 and rec["state_bytes"] > 0
    assert stats["kv_cache"]["prefix_block_hits"] == 2


def test_a_shared_prefix_hits_once_a_request_ended_on_its_boundary():
    """B shares A's first 32 tokens.  A's own snapshot is at 32 (its
    deepest boundary under 40 tokens), so B skips two blocks."""
    outs, stats, hits, _ = _serve(_build(), [A, B])
    np.testing.assert_array_equal(outs[1], _oracle(B))
    assert hits == [0, 2]


def test_a_match_without_a_snapshot_is_a_counted_miss_and_still_right():
    """One snapshot entry (one slot): C's prefill takes it from A, so
    A's repeat finds its K/V blocks resident and no state for them.  It
    is prefilled in full, says so in a counter, and answers right."""
    outs, stats, hits, _ = _serve(_build(max_slots=1), [A, C, A])
    for prompt, out in zip([A, C, A], outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert hits == [0, 0, 0]
    rec = stats["recurrent"]
    assert rec["snapshot_misses"] == 1 and rec["snapshot_hits"] == 0
    assert rec["snapshot_evictions"] >= 1
    assert stats["kv_cache"]["prefix_block_hits"] == 0


def test_a_reused_slot_answers_as_a_fresh_engine_does():
    """Two slots, six requests: every slot has had another tenant, and a
    slot's state at admission is zero or a snapshot, never the previous
    tenant's."""
    prompts = [A, C, B, A, C, B]
    outs, stats, _, _ = _serve(_build(max_slots=2), prompts,
                               together=True)
    for prompt, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["kv_cache"]["blocks_in_use"] == 0


def test_chunked_prefill_hits_a_snapshot_and_equals_one_shot():
    """A streamed prompt that hits a snapshot takes it at admission
    (`restore_state`) and carries its own state from chunk to chunk."""
    long_a = _tokens(14, 90)
    long_b = np.concatenate([long_a[:80], _tokens(15, 30)])
    dep = _build(prefill_chunk_tokens=16, max_new_tokens=4)
    one_shot = _build(max_new_tokens=4)
    outs, stats, hits, _ = _serve(dep, [long_a, long_b])
    want, _, want_hits, _ = _serve(one_shot, [long_a, long_b])
    for got, ref in zip(outs, want):
        np.testing.assert_array_equal(got, ref)
    assert hits == want_hits == [0, 5]
    assert stats["prefill_chunks"]["chunks"] >= 6


@pytest.mark.parametrize("option,kw", [
    ("spec_decode", {"spec_decode": SpecConfig()}),
    ("kv_host_tier_bytes", {"kv_host_tier_bytes": 1 << 20}),
    ("role='prefill'", {"role": "prefill"}),
    ("role='decode'", {"role": "decode"}),
    ("mesh", {"mesh": object()})])
def test_what_cannot_carry_the_state_is_refused(option, kw):
    with pytest.raises(ValueError, match="kv\\+recurrent") as e:
        _build(**kw)
    assert option in str(e.value)


def test_a_recurrent_family_cannot_be_a_spec_draft():
    with pytest.raises(ValueError, match="spec draft"):
        SpecConfig(draft="jamba:nano")


# -- the snapshot index -------------------------------------------------------

def test_snapshots_go_with_their_block_and_by_lru():
    snaps = StateSnapshots(2)
    pager = BlockPager(1 + 8, 16, 128)
    pager.set_snapshots(snaps)
    toks = tuple(range(48))
    blocks = pager.allocate(3)
    pager.register_prefix(toks, blocks)
    assert snaps.reserve(toks[:32]) == snaps.entry_of(toks[:32])
    # the deepest boundary that has a state, not the deepest matched
    prefix_len, matched = pager.match_prefix(toks + (7,))
    assert (prefix_len, matched) == (32, blocks[:2])
    pager.release(matched + blocks)
    # eviction of the boundary's block takes the snapshot with it
    assert pager.allocate(8) is not None
    assert snaps.entry_of(toks[:32]) is None and snaps.evictions == 1
    # least recently used goes when all are held
    a, b = snaps.reserve((1,)), snaps.reserve((2,))
    assert snaps.deepest((1, 9), 1, 1) == 1 and snaps.entry_of((1,)) == a
    assert snaps.reserve((3,)) == b and snaps.entry_of((2,)) is None
    assert snaps.stats()["snapshot_evictions"] == 2


# -- arithmetic ---------------------------------------------------------------

def test_the_familys_arithmetic_is_the_published_models(family):
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        config = json.load(f)
    assert family.param_count(config) == 3_029_337_472
    assert family.mamba_mixer_params(config) == 41_241_792
    assert family.kv_bytes_per_token(config) == 1_024
    assert family.state_bytes_per_slot(config) == 9_318_400
    assert family.layer_counts(config) == {"attention": 2, "mamba": 26}
    cfg = jamba_config("jamba2-3b", dtype=jnp.bfloat16,
                       **{k: v for k, v in family.sizes(config).items()
                          if k != "max_seq"})
    assert jamba_param_count(cfg) == 3_029_337_472
    assert cfg.state_bytes_per_slot == 9_318_400
    assert [i for i in range(cfg.n_layer)
            if i % cfg.attn_period == cfg.attn_offset] == [7, 21]
    shape = family.attention_shape(config)
    assert (shape["n_layer"], shape["n_kv_head"], shape["head_dim"]) \
        == (2, 1, 128)
    # weights once + K/V attended; the state's bytes by the rows
    assert family.decode_step_bytes(config, 1000) \
        == 2 * 3_029_337_472 + 1_024_000
    assert family.ssm_decode_bytes(config, 64) \
        == 26 * 41_241_792 * 2 + 64 * 2 * 9_318_400


def test_init_counts_what_param_count_says(tiny):
    cfg, params = tiny
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == jamba_param_count(cfg) \
        + (cfg.padded_vocab - cfg.vocab_size) * cfg.d_model


# -- graftcheck ---------------------------------------------------------------

@pytest.mark.parametrize("name,writes", [
    ("jamba_paged_decode_step", 0), ("jamba_paged_prefill_bucket", 2)])
def test_jamba_programs_update_pool_and_state_in_place(name, writes):
    """Pool AND recurrent state (snapshots too) alias their results, no
    copy of either is left, and neither is a scan's stacked `ys`."""
    from ray_tpu.tools.graftcheck.jaxpr_audit import audit_program
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs() if s.name == name)
    assert spec.donate_argnums == (1,) and spec.inplace_pool == 1
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        violations, info = audit_program(spec)
    assert not violations, [v.message for v in violations]
    assert info["state_bytes"] > 0
    assert info["alias_bytes"] >= info["pool_bytes"] + info["state_bytes"]
    assert info["pool_layer_writes"] == writes


def test_planted_dropped_state_loses_the_alias():
    """The rule's extension holds the STATE to the alias too: a program
    that hands back everything but the SSM state it was donated (as one
    that stacked a second state beside it would) aliases too few bytes,
    and the rule says so."""
    from ray_tpu.tools.graftcheck.jaxpr_audit import (ProgramSpec,
                                                      audit_program)
    from ray_tpu.tools.graftcheck.programs import default_programs

    spec = next(s for s in default_programs()
                if s.name == "jamba_paged_decode_step")
    fn, args = spec.build()

    def state_dropped(p, c, t):
        logits, out = fn(p, c, t)
        return logits, {k: v for k, v in out.items() if k != "ssm"}

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        violations, info = audit_program(ProgramSpec(
            name="planted", build=lambda: (state_dropped, args),
            donate_argnums=(1,), inplace_pool=1, allow_f32_matmul=True))
    assert "pool-inplace" in {v.rule for v in violations}
    assert info["alias_bytes"] < info["pool_bytes"] + info["state_bytes"]
    assert NO_SNAPSHOT == -1
