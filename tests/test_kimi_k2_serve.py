"""Family ``kimi_k2`` through the serving engine: the continuous
scheduler over the paged latent pool answers as the dense oracle, a
repeated prompt hits its latent blocks, the expert counters land, and
what cannot carry a latent pool is refused at the options check."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import families
from ray_tpu.models.kimi_k2 import kimi_k2_config, kimi_k2_init
from ray_tpu.models.kimi_k2_decode import kimi_k2_generate
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment

MAX_NEW = 6
_OVR = {"dtype": jnp.float32, "held": (0, 1, 2, 3, 4, 5)}


def _tokens(seed, n):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), (n,),
                                         0, 512), np.int32)


def _build(**kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("max_new_tokens", MAX_NEW)
    kw.setdefault("kv_block_size", 16)
    kw.setdefault("prefill_bucket", 16)
    kw.setdefault("scheduler", "continuous")
    kw.setdefault("kv_layout", "paged")
    return build_llm_deployment("kimi_k2", "nano", temperature=0.0,
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
            return outs, inst.engine_stats(), hits
        finally:
            if hasattr(inst, "_engine_task"):
                inst.shutdown_engine()

    return asyncio.run(main())


_ORACLE = {}


def _oracle(prompt):
    key = prompt.tobytes()
    if key not in _ORACLE:
        cfg = kimi_k2_config("nano", **_OVR)
        params = kimi_k2_init(jax.random.PRNGKey(0), cfg)
        _ORACLE[key] = np.asarray(kimi_k2_generate(
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
    outs, stats, _ = _serve(_build(**kw), [A, C, B])
    for prompt, out in zip([A, C, B], outs):
        np.testing.assert_array_equal(out, _oracle(prompt))
    assert stats["requests"]["finished"] == 3


def test_requests_together_answer_as_alone():
    outs, _, _ = _serve(_build(), [A, C, B], together=True)
    for prompt, out in zip([A, C, B], outs):
        np.testing.assert_array_equal(out, _oracle(prompt))


def test_a_repeated_prompt_hits_latent_blocks_and_answers_as_cold():
    """The harness's ``repeat_hit``: 40 tokens, two blocks of 16 latents
    resident, 8 tokens prefilled; the answer is the cold one."""
    outs, stats, hits = _serve(_build(), [A, A, B])
    np.testing.assert_array_equal(outs[0], _oracle(A))
    np.testing.assert_array_equal(outs[1], outs[0])
    np.testing.assert_array_equal(outs[2], _oracle(B))
    assert hits == [0, 2, 2]
    assert stats["kv_cache"]["prefix_block_hits"] == 4
    assert stats["recurrent"]["state_bytes"] == 0


def test_the_expert_counters_land_with_the_tokens():
    _, stats, _ = _serve(_build(), [A, C])
    experts = stats["experts"]
    assert set(experts) == {"decode", "prefill"}
    for kind, block in experts.items():
        assert block["held"] == 6 and block["of"] == 16
        assert 0 < block["experts_touched_share"] <= 1
        assert block["load_max_over_mean"] >= 1
        # row tiles the experts' rows fill over the experts touched:
        # one apiece unless an expert's rows overflow a tile of 8
        assert 1.0 <= block["row_tiles_per_touched"] < 3.0
    assert experts["decode"]["row_tiles_per_touched"] == 1.0
    assert experts["prefill"]["programs"] == 2
    # a prefill of 40 and one of 21 tokens, 2 expert layers, 4 of 16
    # experts a token, 6 held: about 61 * 2 * 4 * 6 / 16 assignments
    assert 100 < experts["prefill"]["assignments_local"] < 270
    assert experts["decode"]["programs"] >= 2 * (MAX_NEW - 1)
    from ray_tpu.util.metrics import _registry

    dump = _registry.snapshot()["serve_expert_programs_total"]
    assert any(dict(map(tuple, tags)).get("program") == "decode"
               for tags, _ in dump["values"])


def test_the_blocks_walked_land_with_the_tokens():
    """Two requests one after the other, 40 and 21 prompt tokens, 6 new
    each: the first comes out of the prefill, five decode waves step
    the row at positions n .. n + 4, which fill ceil(pos / 16) blocks
    (3 each for 40 .. 44, 2 each for 21 .. 25) of the table's
    max_seq / 16 entries."""
    _, stats, _ = _serve(_build(), [A, C])
    walk = stats["kv_walk"]
    tabled = kimi_k2_config("nano", **_OVR).max_seq // 16
    assert walk["waves"] == 2 * (MAX_NEW - 1)
    assert walk["blocks_walked"] == 5 * 3 + 5 * 2
    assert walk["blocks_tabled"] == walk["waves"] * tabled
    assert walk["walked_share"] == round(25 / (10 * tabled), 4)
    # two rows in one wave: both rows' blocks, both rows' tables
    _, stats, _ = _serve(_build(), [A, C], together=True)
    walk = stats["kv_walk"]
    assert walk["blocks_walked"] == 5 * 3 + 5 * 2
    assert walk["blocks_tabled"] == 10 * tabled
    from ray_tpu.util.metrics import _registry

    assert "serve_kv_walk_blocks_walked_total" in _registry.snapshot()


@pytest.mark.parametrize("kw,prefills", [({}, 2),
                                         ({"prefill_chunk_tokens": 16}, 5)],
                         ids=["whole", "chunked"])
def test_what_attended_the_prefills_lands_with_their_tokens(kw, prefills):
    """Off the chip every paged prefill (a chunk is one) takes the jnp
    walk, and the counter says so: 40 and 21 tokens whole, or in chunks
    of 16 (3 + 2)."""
    _, stats, _ = _serve(_build(**kw), [A, C])
    assert stats["prefill_attn"] == {
        "kernel": 0, "jnp": prefills, "pairs_walked": 0, "pairs_square": 0,
        "walked_share": 0.0}
    from ray_tpu.util.metrics import _registry

    assert "serve_prefill_attn_jnp_total" in _registry.snapshot()


def test_a_dense_cache_walks_no_blocks():
    _, stats, _ = _serve(_build(kv_layout="dense"), [C])
    assert stats["kv_walk"] == {"waves": 0, "blocks_walked": 0,
                                "blocks_tabled": 0, "walked_share": 0.0}


def test_a_family_without_experts_counts_none():
    dep = build_llm_deployment(
        "gpt2", "nano", temperature=0.0, scheduler="continuous",
        kv_layout="paged", max_slots=2, max_new_tokens=3)
    _, stats, _ = _serve(dep, [C % 256])
    assert stats["experts"] == {}
    # ... and, with one attention path, counts no prefill by its path
    assert stats["prefill_attn"] == {
        "kernel": 0, "jnp": 0, "pairs_walked": 0, "pairs_square": 0,
        "walked_share": 0.0}


@pytest.mark.parametrize("option,kw", [
    ("spec_decode", {"spec_decode": SpecConfig()}),
    ("kv_host_tier_bytes", {"kv_host_tier_bytes": 1 << 20}),
    ("role='prefill'", {"role": "prefill"}),
    ("role='decode'", {"role": "decode"}),
    ("mesh", {"mesh": object()})])
def test_what_cannot_carry_the_latent_pool_is_refused(option, kw):
    with pytest.raises(ValueError, match="latent pool") as e:
        _build(**kw)
    assert option in str(e.value)


def test_a_latent_family_cannot_be_a_spec_draft():
    with pytest.raises(ValueError, match="spec draft"):
        SpecConfig(draft="kimi_k2:nano")


def test_the_families_table_names_the_cache():
    assert families.cache_kind("kimi_k2") == families.LATENT
    fam = families.family("kimi_k2")
    assert fam.verify is None and fam.cache_kind == "latent"


def test_no_family_is_named_under_serve():
    import os
    import re

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    serve = os.path.join(here, "ray_tpu", "serve")
    for name in os.listdir(serve):
        if name.endswith(".py"):
            with open(os.path.join(serve, name)) as f:
                assert not re.search(r"kimi", f.read(), re.I), name
