"""Speculative decoding on the continuous serve engine (round 11).

The contract under test: with ``spec_decode=SpecConfig(...)`` the
engine proposes k tokens per slot per round (zero-weight n-gram draft
or a small draft MODEL) and verifies all k+1 positions in ONE jitted
target dispatch — and at temperature 0 every caller still gets the
BIT-IDENTICAL continuation the non-spec dense single-request oracle
produces, for both families and both KV layouts.  Telemetry must
account for every proposed token (proposed == accepted + rejected),
and an aligned draft (same family/preset/seed as the target) must
push target dispatches per emitted token under 1/2 at k=4.

Engines are driven directly (``dep.func_or_class()`` on a private
event loop) — the idiom test_serve_paged.py established — so each
test owns its engine, its slots, and its block pool.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.decode_common import (SamplingParams,
                                          sample_token)  # noqa: E402
from ray_tpu.serve.llm import (SpecConfig,
                               build_llm_deployment)  # noqa: E402

MAX_NEW = 6
_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}

# Every spec engine in this file runs k=4: the jitted-program cache in
# serve/llm.py is keyed by SpecConfig, so one verify compile per
# (family, layout) serves the parity, stop, eos, and bench tests.
K = 4


def _build(family="gpt2", **kw):
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("config_overrides", _OVR)
    return build_llm_deployment(family, "nano", **kw)


def _drive(dep, prompts, *, sampling=None, timeout=300):
    """Run all prompts concurrently on a fresh engine instance;
    sampling (optional) is a parallel list of per-request
    SamplingParams/None.  Returns (results, engine_stats)."""
    sps = sampling or [None] * len(prompts)

    async def main():
        inst = dep.func_or_class()
        try:
            outs = await asyncio.wait_for(
                asyncio.gather(*[
                    inst(p) if sp is None else inst(p, sampling=sp)
                    for p, sp in zip(prompts, sps)]),
                timeout)
            stats = inst.engine_stats()
        finally:
            inst.shutdown_engine()
        return outs, stats

    return asyncio.run(main())


def _family_oracle(family):
    """(cfg, params, generate) for the dense single-request greedy
    reference — what every spec/non-spec engine must reproduce."""
    if family == "gpt2":
        from ray_tpu.models import gpt2_config, gpt2_init
        from ray_tpu.models.gpt2_decode import generate
        cfg = gpt2_config("nano", **_OVR)
        return cfg, gpt2_init(jax.random.PRNGKey(0), cfg), generate
    from ray_tpu.models import llama_config, llama_init
    from ray_tpu.models.llama_decode import llama_generate
    cfg = llama_config("nano", **_OVR)
    return cfg, llama_init(jax.random.PRNGKey(0), cfg), llama_generate


_REF_CACHE = {}


def _references(family, prompts, max_new=MAX_NEW):
    cfg, params, generate = _family_oracle(family)
    out = []
    for p in prompts:
        key = (family, max_new, tuple(int(t) for t in p))
        if key not in _REF_CACHE:
            _REF_CACHE[key] = np.asarray(generate(
                params, jnp.asarray(p, jnp.int32)[None], cfg,
                max_new_tokens=max_new, temperature=0.0))[0]
        out.append(_REF_CACHE[key])
    return out


def _prompts(seed=7, lens=(3, 7, 5)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 500, (n,)).astype(np.int32) for n in lens]


# ---------------------------------------------------------------------------
# tentpole acceptance: greedy spec == dense single-request oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_layout", ["dense", "paged"])
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_spec_ngram_greedy_parity(family, kv_layout):
    """n-gram draft, both families x both KV layouts: outputs are
    bit-identical to the oracle and the spec telemetry balances."""
    prompts = _prompts()
    dep = _build(family, kv_layout=kv_layout, kv_block_size=16,
                 max_slots=4,
                 spec_decode=SpecConfig(draft="ngram", k=K))
    outs, stats = _drive(dep, prompts)
    refs = _references(family, prompts)
    for p, o, r in zip(prompts, outs, refs):
        assert o.shape == (len(p) + MAX_NEW,)
        np.testing.assert_array_equal(o[:len(p)], p)
        np.testing.assert_array_equal(o, r)

    assert stats["requests"]["finished"] == len(prompts)
    spec = stats["spec"]
    assert spec["rounds"] > 0
    assert spec["proposed"] > 0
    assert spec["proposed"] == spec["accepted"] + spec["rejected"]
    assert 0.0 <= spec["accept_rate"] <= 1.0
    # every round proposes exactly k per active slot
    assert spec["proposed"] % K == 0


# llama compiles a second full draft-scan program family; the gpt2
# case + the ngram parity matrix above cover the tier-1 contract
@pytest.mark.parametrize("family", [
    "gpt2", pytest.param("llama", marks=pytest.mark.slow)])
def test_spec_aligned_model_draft_accepts_everything(family):
    """A draft MODEL with the target's own family/preset/seed proposes
    the target's argmax every time: acceptance is exactly 1.0 and the
    output is still the oracle's, token for token."""
    prompts = _prompts(seed=11, lens=(4, 6))
    dep = _build(family, max_slots=2,
                 spec_decode=SpecConfig(draft=f"{family}:nano", k=K))
    outs, stats = _drive(dep, prompts)
    refs = _references(family, prompts)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    spec = stats["spec"]
    assert spec["rejected"] == 0
    assert spec["accept_rate"] == 1.0


def test_spec_sharded_engine_smoke():
    """Spec decode on the tensor-parallel engine over 8 virtual
    devices: greedy streams stay bit-identical to the single-chip
    oracle (logits all-reduce in a different order; argmax must not
    care), and spec telemetry still balances."""
    from ray_tpu.parallel import MeshSpec, fake_mesh

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices (conftest forces them in CI)")
    mesh = fake_mesh(8, MeshSpec(data=4, tensor=2))
    prompts = _prompts(seed=3, lens=(5, 7))
    dep = _build("gpt2", max_slots=2, mesh=mesh,
                 spec_decode=SpecConfig(draft="ngram", k=K))
    outs, stats = _drive(dep, prompts)
    refs = _references("gpt2", prompts)
    for o, r in zip(outs, refs):
        np.testing.assert_array_equal(o, r)
    assert stats["mesh"]["axes"] == {"data": 4, "tensor": 2}
    spec = stats["spec"]
    assert spec["rounds"] > 0
    assert spec["proposed"] == spec["accepted"] + spec["rejected"]


# ---------------------------------------------------------------------------
# stop sequences / eos: host-side matching frees slots mid-flight
# ---------------------------------------------------------------------------

def test_stop_sequence_truncates_midflight():
    """A stop sequence drawn from the oracle's own continuation must
    cut the output right after the match — with and without spec (the
    spec emission loop checks stops token by token)."""
    prompts = _prompts(seed=5, lens=(6,))
    ref = _references("gpt2", prompts)[0]
    cont = [int(t) for t in ref[len(prompts[0]):]]
    stop = (cont[1], cont[2])

    # earliest generated prefix whose suffix is `stop` (degenerate
    # continuations can repeat tokens, matching before position 3)
    cut = next(i + 1 for i in range(len(cont))
               if tuple(cont[max(0, i + 1 - len(stop)):i + 1]) == stop)
    assert cut < MAX_NEW                        # stop really truncates
    want = ref[:len(prompts[0]) + cut]

    for spec in (None, SpecConfig(draft="ngram", k=K)):
        dep = _build("gpt2", stop_sequences=[stop], max_slots=2,
                     spec_decode=spec)
        outs, stats = _drive(dep, prompts)
        np.testing.assert_array_equal(outs[0], want)
        assert stats["requests"]["finished"] == 1


def test_eos_frees_slots_for_same_wave_refill():
    """3 concurrent requests through 2 paged slots with an eos_id that
    ends some continuations early: freed slots must be refilled from
    the queue in the SAME wave, every caller gets the oracle
    continuation truncated at its own first eos, and the pager ends
    the run with zero blocks in use."""
    prompts = _prompts(seed=9, lens=(3, 7, 4))
    refs = _references("gpt2", prompts)
    # eos = the first generated token of prompt 0 -> that request
    # finishes after one token, freeing its slot almost immediately
    eos = int(refs[0][len(prompts[0])])

    def truncate(p, r):
        cont = list(r[len(p):])
        cut = cont.index(eos) + 1 if eos in cont else len(cont)
        return np.concatenate([p, np.asarray(cont[:cut], p.dtype)])

    dep = _build("gpt2", kv_layout="paged", kv_block_size=16,
                 max_slots=2, eos_id=eos,
                 spec_decode=SpecConfig(draft="ngram", k=K))
    outs, stats = _drive(dep, prompts)
    for p, o, r in zip(prompts, outs, refs):
        np.testing.assert_array_equal(o, truncate(p, r))
    assert stats["requests"]["finished"] == 3
    # 4 requests through 2 slots: mid-flight refill must have happened
    assert stats["max_active_slots"] == 2
    assert stats["kv_cache"]["blocks_in_use"] == 0


# ---------------------------------------------------------------------------
# per-request sampling on the continuous engine
# ---------------------------------------------------------------------------

def test_mixed_sampling_wave_keeps_greedy_rows_exact():
    """One wave mixing default-greedy requests with a per-request
    SamplingParams override: the greedy rows must still match the
    oracle bit for bit, and the sampled row must be a valid in-vocab
    continuation of its own prompt."""
    prompts = _prompts(seed=13, lens=(4, 6))
    sp = SamplingParams(temperature=0.8, top_k=8)
    dep = _build("gpt2", max_slots=2, seed=0)
    outs, stats = _drive(dep, prompts,
                         sampling=[None, sp])
    refs = _references("gpt2", prompts)
    np.testing.assert_array_equal(outs[0], refs[0])
    cfg, *_ = _family_oracle("gpt2")
    sampled = outs[1]
    assert sampled.shape == (len(prompts[1]) + MAX_NEW,)
    np.testing.assert_array_equal(sampled[:len(prompts[1])],
                                  prompts[1])
    assert (sampled[len(prompts[1]):] < cfg.vocab_size).all()
    assert stats["requests"]["finished"] == 2


def test_sampling_and_spec_validation_errors():
    p = np.array([1, 2, 3], np.int32)

    # malformed SpecConfig values fail fast at construction
    with pytest.raises(ValueError):
        SpecConfig(k=0)
    with pytest.raises(ValueError):
        SpecConfig(draft="bogus")
    with pytest.raises(ValueError):
        SpecConfig(draft="bert:nano")
    with pytest.raises(ValueError):
        SpecConfig(ngram_order=0)

    # spec requires the continuous scheduler, and a real SpecConfig
    with pytest.raises(ValueError):
        build_llm_deployment("gpt2", "nano", scheduler="batch",
                             spec_decode=SpecConfig())
    with pytest.raises(ValueError):
        build_llm_deployment("gpt2", "nano", scheduler="continuous",
                             spec_decode="ngram")
    # empty stop sequences are a config bug, not a no-op
    with pytest.raises(ValueError):
        build_llm_deployment("gpt2", "nano",
                             stop_sequences=[[]])

    # the batch scheduler runs one fused generate per micro-batch:
    # per-request overrides are rejected at call time
    batch_dep = build_llm_deployment(
        "gpt2", "nano", max_new_tokens=2, config_overrides=_OVR)
    inst = batch_dep.func_or_class()
    with pytest.raises(ValueError, match="continuous"):
        asyncio.run(inst(p, sampling=SamplingParams(temperature=0.5)))

    # spec bakes ONE sampling config into the verify program
    spec_dep = _build("gpt2", spec_decode=SpecConfig())
    sinst = spec_dep.func_or_class()
    with pytest.raises(ValueError, match="spec_decode"):
        asyncio.run(
            sinst(p, sampling=SamplingParams(temperature=0.5)))

    # non-SamplingParams sampling objects are rejected, not coerced
    plain = _build("gpt2")
    pinst = plain.func_or_class()
    with pytest.raises(ValueError, match="SamplingParams"):
        asyncio.run(pinst(p, sampling={"temperature": 0.5}))


# ---------------------------------------------------------------------------
# jitted-program cache key covers the full sampling/spec config
# ---------------------------------------------------------------------------

def test_jitted_fns_cache_keyed_by_sampling_and_spec():
    """Regression (round-11 satellite): engines differing in top_k /
    top_p / SpecConfig must never alias one compiled program — and a
    bare float temperature (the pre-round-11 call shape) still hits
    the same cache entry as its SamplingParams equivalent."""
    from ray_tpu.models import gpt2_config
    from ray_tpu.models.families import family
    from ray_tpu.serve.llm import _jitted_engine_fns

    cfg = gpt2_config("nano", **_OVR)

    def fns(sampling, **kw):
        # a fresh adapter each call: the key is its programs, not it
        return _jitted_engine_fns(family("gpt2"), cfg, sampling, **kw)

    base = fns(0.0)
    assert fns(0.0) is base                     # cache hit
    assert fns(SamplingParams(temperature=0.0)) is base   # coerced
    assert fns(SamplingParams(temperature=0.7, top_k=2)) \
        is not fns(SamplingParams(temperature=0.7, top_k=4))
    assert fns(SamplingParams(temperature=0.7, top_p=0.9)) \
        is not fns(SamplingParams(temperature=0.7))

    k2 = fns(0.0, spec=SpecConfig(k=2))
    k4 = fns(0.0, spec=SpecConfig(k=4))
    assert k2 is not base and k4 is not base and k2 is not k4
    assert k2.spec_verify is not None
    assert base.spec_verify is None
    # same spec -> same entry (SpecConfig is hashable by value)
    assert fns(0.0, spec=SpecConfig(k=2)) is k2


# ---------------------------------------------------------------------------
# bench acceptance: aligned draft amortizes target dispatches
# ---------------------------------------------------------------------------

def test_bench_spec_dispatches_per_token_under_half():
    """The CPU bench criterion from the round-11 issue: with an
    aligned draft at k=4, target dispatches per emitted token must
    drop below 1/2 (the non-spec engine is exactly 1.0) with
    acceptance ~1.0."""
    import bench

    tok_s, stats, dispatches_per_token, n_chips = \
        bench.time_decode_spec(4, prompt_len=16, new_tokens=12,
                               preset="nano", spec_k=4,
                               spec_draft="aligned",
                               config_overrides=_OVR)
    assert tok_s > 0 and n_chips >= 1
    assert stats["spec"]["accept_rate"] == 1.0
    assert dispatches_per_token < 0.5


# ---------------------------------------------------------------------------
# sample_token distribution properties (jit-static top_k / top_p)
# ---------------------------------------------------------------------------

def _batched_logits(row, n=512):
    return jnp.tile(jnp.asarray(row, jnp.float32)[None, :], (n, 1))


def test_sample_token_top_k_restricts_support():
    row = np.array([3.0, 2.5, 1.0, 0.5, -1.0, -2.0, -3.0, -4.0])
    toks = np.asarray(sample_token(_batched_logits(row),
                                   jax.random.PRNGKey(0), 1.0, None,
                                   top_k=2))
    assert set(toks.tolist()) == {0, 1}         # both survive, only both


def test_sample_token_top_p_keeps_smallest_nucleus():
    # probs ~ [0.6, 0.3, 0.1, ...]: mass before token2 is 0.9 >= 0.7,
    # so top_p=0.7 keeps exactly {0, 1} (the top-1 always survives)
    row = np.log(np.array([0.6, 0.3, 0.06, 0.02, 0.02]))
    toks = np.asarray(sample_token(_batched_logits(row),
                                   jax.random.PRNGKey(1), 1.0, None,
                                   top_p=0.7))
    assert set(toks.tolist()) == {0, 1}


def test_sample_token_padded_tail_never_sampled():
    # the padded tail holds the LARGEST logits; the mask must win for
    # greedy and for every filtered sampling combination
    row = np.array([1.0, 0.5, 0.2, 9.0, 9.0, 9.0])
    tail = jnp.asarray([True, True, True, False, False, False])
    greedy = np.asarray(sample_token(jnp.asarray(row, jnp.float32),
                                     None, 0.0, tail))
    assert int(greedy) == 0
    for kw in ({}, {"top_k": 2}, {"top_p": 0.9},
               {"top_k": 4, "top_p": 0.95}):
        toks = np.asarray(sample_token(_batched_logits(row, 256),
                                       jax.random.PRNGKey(2), 1.0,
                                       tail, **kw))
        assert (toks < 3).all()


def test_sample_token_greedy_invariant_to_filters():
    row = np.array([0.1, 2.0, 1.5, -0.5])
    lg = jnp.asarray(row, jnp.float32)
    want = int(np.argmax(row))
    for kw in ({}, {"top_k": 1}, {"top_k": 3}, {"top_p": 0.5}):
        assert int(sample_token(lg, None, 0.0, None, **kw)) == want
