"""Chunked streaming prefill: long prompts admitted as block-sized
chunks interleaved with decode waves (``prefill_chunk_tokens``).

The correctness oracle is unchanged from the rest of the paged suite:
dense solo greedy ``generate``.  Chunking only re-schedules *when*
prompt tokens are written into KV blocks — each chunk is the existing
``paged_prefill`` program with ``prefix_len`` = tokens already filled
— so every continuation must stay bit-identical to the one-shot path,
cold and with a resident shared prefix, with and without speculative
decoding, for both decoder families.

The acceptance test is the headline: under a two-tenant mix where
long batch prompts land ahead of short interactive ones, enabling
chunking must put strictly fewer of the batch tenant's prompt tokens
on the device ahead of every interactive prompt than the one-shot run
of the same workload does.  That ordering is what the interactive
tenant's TTFT rests on, and the engine's launch records state it
exactly; two wall-clock p99s of a CPU run under the suite's workers
do not.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.serve.llm import SpecConfig, build_llm_deployment  # noqa: E402
from ray_tpu.serve.telemetry import CRITICAL_PATH_COMPONENTS  # noqa: E402

MAX_NEW = 6
CHUNK = 32
_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}

#: mixed lengths around the chunk boundary: 70 -> 3 chunks (32/32/6),
#: 9 -> not chunked, 100 -> 4 chunks, 33 -> 2 chunks (32/1)
_LENGTHS = (70, 9, 100, 33)


def _build(family="gpt2", chunk=CHUNK, **kw):
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("max_slots", 4)
    kw.setdefault("config_overrides", _OVR)
    return build_llm_deployment(family, "nano",
                                prefill_chunk_tokens=chunk, **kw)


def _prompts(seed=0, lengths=_LENGTHS):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 500, size=n).astype(np.int32)
            for n in lengths]


def _drive(dep, prompts, *, sequential=False):
    """Run prompts on a fresh engine; returns (outs, stats, records)."""
    async def main():
        inst = dep.func_or_class()
        try:
            if sequential:
                outs = [await inst(p) for p in prompts]
            else:
                outs = await asyncio.gather(*[inst(p) for p in prompts])
            stats = inst.engine_stats()
            recs = inst.trace_records()
        finally:
            inst.shutdown_engine()
        return [np.asarray(o) for o in outs], stats, recs

    return asyncio.run(main())


def _oracle(family, prompt, max_new=MAX_NEW):
    """Dense solo greedy continuation — the parity reference."""
    if family == "gpt2":
        from ray_tpu.models import gpt2_config, gpt2_init
        from ray_tpu.models.gpt2_decode import generate
        cfg = gpt2_config("nano", **_OVR)
        params = gpt2_init(jax.random.PRNGKey(0), cfg)
    else:
        from ray_tpu.models import llama_config, llama_init
        from ray_tpu.models.llama_decode import llama_generate \
            as generate
        cfg = llama_config("nano", **_OVR)
        params = llama_init(jax.random.PRNGKey(0), cfg)
    out = generate(params, jnp.asarray(np.asarray(prompt)[None]), cfg,
                   max_new_tokens=max_new, temperature=0.0)
    return np.asarray(out)[0]


# ---------------------------------------------------------------------------
# bitwise parity: cold, resident prefix, spec decode, both families
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunked_cold_prompts_match_dense_solo(family):
    prompts = _prompts()
    outs, stats, _recs = _drive(_build(family), prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(family, p))
    pc = stats["prefill_chunks"]
    assert pc["requests"] == 3          # the 9-token prompt one-shots
    assert pc["chunks"] == 9            # 3 + 4 + 2
    assert pc["tokens"] == 70 + 100 + 33
    assert pc["max_chunks_per_request"] == 4


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunked_resident_prefix_matches_dense_solo(family):
    """The second request reuses the first's registered prefix blocks,
    so its ChunkCursor starts at filled=32 — fewer chunks, same bits."""
    rng = np.random.RandomState(7)
    shared = rng.randint(2, 500, 32)
    a = np.concatenate([shared, rng.randint(2, 500, 40)]).astype(np.int32)
    b = np.concatenate([shared, rng.randint(2, 500, 38)]).astype(np.int32)

    outs, stats, _recs = _drive(_build(family), [a, b],
                                sequential=True)
    np.testing.assert_array_equal(outs[0], _oracle(family, a))
    np.testing.assert_array_equal(outs[1], _oracle(family, b))
    assert stats["kv_cache"]["prefix_block_hits"] >= 2
    pc = stats["prefill_chunks"]
    assert pc["requests"] == 2
    # A (72 cold) chunks 32/32/8; B fills only its 38-token tail
    assert pc["chunks"] == 5
    assert pc["tokens"] == 72 + 38


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_chunked_spec_decode_matches_dense_solo(family):
    """Greedy ngram spec decoding over chunked admissions: rollback
    still reproduces the dense argmax stream bit-for-bit."""
    prompts = _prompts(seed=3, lengths=(70, 33))
    dep = _build(family, spec_decode=SpecConfig(draft="ngram", k=2))
    outs, stats, _recs = _drive(dep, prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle(family, p))
    assert stats["prefill_chunks"]["requests"] == 2
    assert stats["spec"]["rounds"] > 0


def test_chunk_equal_to_prompt_stays_one_shot():
    """Prompts at or under the chunk budget take the legacy admission
    path: zero chunk counters, identical outputs."""
    prompts = _prompts(seed=5, lengths=(32, 16, 9))
    outs, stats, _recs = _drive(_build(), prompts)
    for p, out in zip(prompts, outs):
        np.testing.assert_array_equal(out, _oracle("gpt2", p))
    assert stats["prefill_chunks"] == {
        "requests": 0, "chunks": 0, "tokens": 0,
        "max_chunks_per_request": 0}


# ---------------------------------------------------------------------------
# telemetry: critical-path decomposition over chunked records
# ---------------------------------------------------------------------------

def test_chunked_critical_path_sums_and_splits_wait():
    outs, _stats, recs = _drive(_build(), _prompts())
    assert len(outs) == 4
    chunked = [r for r in recs if r.get("prefill_chunks")]
    assert len(chunked) == 3
    for r in recs:
        cp = r["critical_path"]
        comp_sum = sum(cp[k] for k in CRITICAL_PATH_COMPONENTS)
        # live clocks: each component rounds to 4 decimals
        assert comp_sum == pytest.approx(cp["e2e_ms"], abs=1e-2)
    for r in chunked:
        cp = r["critical_path"]
        # chunk windows never exceed the admit -> first-token window
        assert cp["prefill_ms"] >= 0.0
        assert cp["prefill_wait_ms"] >= 0.0
    for r in recs:
        if not r.get("prefill_chunks"):
            assert r["critical_path"]["prefill_wait_ms"] == 0.0


# ---------------------------------------------------------------------------
# construction-time validation
# ---------------------------------------------------------------------------

def test_chunking_requires_paged_layout():
    with pytest.raises(ValueError, match="paged"):
        build_llm_deployment(
            "gpt2", "nano", scheduler="continuous", kv_layout="dense",
            prefill_chunk_tokens=32, config_overrides=_OVR)


@pytest.mark.parametrize("bad", [0, -16, 24])
def test_chunk_tokens_must_be_positive_block_multiple(bad):
    with pytest.raises(ValueError, match="multiple"):
        build_llm_deployment(
            "gpt2", "nano", scheduler="continuous", kv_layout="paged",
            kv_block_size=16, prefill_chunk_tokens=bad,
            config_overrides=_OVR)


# ---------------------------------------------------------------------------
# perfledger: the per-tenant TTFT series trend lower-is-better
# ---------------------------------------------------------------------------

def test_perfledger_tenant_ttft_direction_and_fields():
    from ray_tpu.tools.perfledger import (_SWEEP_FIELDS,
                                          higher_is_better)

    assert "interactive_ttft_ms_p99" in _SWEEP_FIELDS
    assert "batch_ttft_ms_p99" in _SWEEP_FIELDS
    assert higher_is_better("interactive_ttft_ms_p99") is False
    assert higher_is_better("batch_ttft_ms_p99") is False
    # the attainment fractions keep their higher-is-better override
    assert higher_is_better("interactive_ttft_slo_attainment") is True


# ---------------------------------------------------------------------------
# acceptance: chunking strictly lowers what an interactive prompt's
# first token waits behind
# ---------------------------------------------------------------------------

_LONG = 96           # 3 exact chunks of 32; bucket 96 when one-shot
_N_LONG, _N_SHORT = 6, 4


def _ab_ttft(chunk):
    """Run the two-tenant mix on one engine: a warmup tenant first
    (excluded from the measurement), then the measured phase enqueues
    all longs ahead of all shorts.  Returns (engine stats, for each
    interactive request the batch tenant's prompt tokens launched
    before its own prefill)."""
    dep = _build(chunk=chunk, max_slots=_N_LONG + _N_SHORT,
                 max_new_tokens=4)
    rng = np.random.RandomState(17)
    longs = [rng.randint(2, 500, _LONG).astype(np.int32)
             for _ in range(_N_LONG)]
    shorts = [rng.randint(2, 500, 10).astype(np.int32)
              for _ in range(_N_SHORT)]
    warm_long = rng.randint(2, 500, _LONG).astype(np.int32)
    warm_short = rng.randint(2, 500, 10).astype(np.int32)

    async def main():
        inst = dep.func_or_class()
        try:
            await inst(warm_long, tenant="warmup")
            await inst(warm_short, tenant="warmup")
            tasks = [asyncio.ensure_future(inst(p, tenant="batch"))
                     for p in longs]
            await asyncio.sleep(0)       # longs enqueue first
            tasks += [asyncio.ensure_future(
                inst(p, tenant="interactive")) for p in shorts]
            await asyncio.gather(*tasks)
            return (inst.engine_stats(), inst.trace_records(),
                    inst.launch_records())
        finally:
            inst.shutdown_engine()

    stats, records, launches = asyncio.run(main())
    tnt = stats["latency_anatomy"]["by_tenant"]
    assert tnt["interactive"]["requests"] == _N_SHORT
    assert tnt["batch"]["requests"] == _N_LONG
    tenant = {r["id"]: r["tenant"] for r in records}
    batch_tokens, ahead = 0, []
    for launch in sorted(launches, key=lambda la: la["seq"]):
        of = tenant.get(launch.get("req"))   # None: a decode wave
        if of == "batch":
            batch_tokens += launch["n_tail"]
        elif of == "interactive":
            ahead.append(batch_tokens)
    assert len(ahead) == _N_SHORT
    return stats, ahead


def test_interactive_ttft_p99_strictly_lower_with_chunking():
    """One-shot admission runs each long prompt's full prefill inline
    before later queue pops, so the short interactive prompts behind
    six 96-token prefills inherit all of them in their TTFT; chunked
    admission defers that work into decode-interleaved chunks and the
    shorts admit almost immediately."""
    stats_off, ahead_off = _ab_ttft(None)
    stats_on, ahead_on = _ab_ttft(CHUNK)
    assert stats_off["prefill_chunks"]["requests"] == 0
    # warmup long + 6 measured longs all chunk
    assert stats_on["prefill_chunks"]["requests"] == _N_LONG + 1
    # one-shot: every long prompt whole; chunked: a chunk or two
    assert min(ahead_off) == _N_LONG * _LONG
    assert max(ahead_on) < min(ahead_off), (ahead_on, ahead_off)
