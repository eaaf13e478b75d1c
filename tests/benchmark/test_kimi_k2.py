"""The benchmark's files for family ``kimi_k2``: the program's forward
held to the plain reference at the cell's tolerance, a wrong model
failing it, and the family file's arithmetic from the published sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, correct
from benchmark.cells import ROOT, load_json

CELL = "kimi-k2-code.serve-offline-codegen"
#: the cell's tolerance (``families/kimi_k2.py logit_tie_tol``) stands
#: between what its engine leaves and what fp8 weights leave at the
#: published widths, where logits have std 1.7.  The rehearsal widths'
#: logits have std 0.16, so the same construction is made anew from
#: the same two readings here, answers of 48 tokens over six seeds: the
#: bf16 program's largest gap 0 to 0.00087, with weights rounded to fp8
#: 0.0052 to 0.0151.
NANO_TIE_TOL = 0.003


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    """The rehearsal configuration's program, float32 and bf16, over
    one set of weights."""
    config = load_json(cells.tree(ROOT, "rehearsal", "kimi_k2.json"))
    family = cell.family
    prog = family.program(config, {"dtype": jnp.float32, "max_seq": 128})
    bf16 = family.program(config, {"max_seq": 128})
    params = prog.init(jax.random.PRNGKey(3))
    return config, family, cell.reference, prog, bf16, params


def _forward(cfg, params, tokens):
    from ray_tpu.models.kimi_k2 import kimi_k2_forward

    return np.asarray(kimi_k2_forward(params, jnp.asarray(tokens), cfg)
                      )[..., :cfg.vocab_size]


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


def test_the_cells_files_are_found_by_name(cell):
    assert cell.config["program"] == {"family": "kimi_k2",
                                      "preset": "kimi-k2-code"}
    assert cell.family.REFERENCE == "kimi_k2" and cell.chips == 1
    assert cell.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"mla_time_share.offline", "moe_time_share.offline",
            "moe_expert_roofline.offline", "mla_decode_roofline.offline",
            "expert_load_max_over_mean.offline"} <= names
    assert "decode_hbm_roofline.offline" not in names
    for name in names:
        assert callable(cells.load_reader(name))


def test_reference_logits_match_the_program(tiny):
    config, family, reference, prog, _, params = tiny
    toks = _tokens(1, 2, 40)
    want = np.asarray(reference.logits(
        params, jnp.asarray(toks), vocab_size=prog.cfg.vocab_size,
        **family.reference_kwargs(config)))
    np.testing.assert_allclose(_forward(prog.cfg, params, toks), want,
                               atol=1e-5)


def test_reference_loss_matches_the_program(tiny):
    config, family, reference, prog, _, params = tiny
    toks = _tokens(2, 2, 33)
    want = float(reference.loss(params, jnp.asarray(toks),
                                vocab_size=prog.cfg.vocab_size,
                                **family.reference_kwargs(config)))
    got = float(prog.loss(params, {"tokens": jnp.asarray(toks)}))
    assert abs(got - want) / want < correct.LOSS_RTOL


def _greedy_check(cell, tiny, params_for_engine, seed):
    """The program's bf16 greedy continuation of a prompt, teacher
    forced through the float32 reference over the TRUE weights: what
    the harness's `correct` does to a served answer."""
    from ray_tpu.models.kimi_k2_decode import kimi_k2_generate

    config, family, reference, _, bf16, params = tiny
    prompt = _tokens(seed, 1, 24)
    out = np.asarray(kimi_k2_generate(
        params_for_engine, jnp.asarray(prompt), bf16.cfg,
        max_new_tokens=48, temperature=0.0))[0]
    lg = correct.reference_generated_logits(
        reference, params, out, 24, vocab_size=bf16.cfg.vocab_size,
        max_seq=bf16.cfg.max_seq, **family.reference_kwargs(config))
    return correct.check_greedy(lg, out[24:], NANO_TIE_TOL)


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_the_bf16_program_passes_the_cells_tolerance(cell, tiny, seed):
    res = _greedy_check(cell, tiny, tiny[-1], seed)
    assert res["ok"], res


def _fp8(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)


def test_fp8_weights_fail_the_cells_tolerance(cell, tiny):
    """Weights rounded to fp8 answer otherwise than the reference over
    the true weights, by more than the tolerance, on every seed."""
    broken = _fp8(tiny[-1])
    results = [_greedy_check(cell, tiny, broken, seed)
               for seed in (5, 6, 7)]
    assert not any(r["ok"] for r in results), results


def test_the_familys_arithmetic_is_the_published_models(cell):
    family, config = cell.family, cell.config
    assert family.mla_params(config) == 101_124_096
    assert family.expert_params(config) == 44_040_192
    assert family.layer_params(config) == {"dense": 497_500_160,
                                           "expert": 676_413_824}
    assert family.param_count(config) == 4_173_177_728
    assert family.kv_bytes_per_token(config) == 1152 * 6
    shape = family.attention_shape(config)
    assert shape["latent_dim"] == 576 and shape["n_kv_head"] == 1
    assert shape["head_dim"] == 192 and shape["v_head_dim"] == 128
    s = family.sizes(config)
    assert s["n_routed"] == 384 and s["held"] == tuple(range(12))
    assert s["vocab_size"] == 20480 and s["n_layer"] == 6
    # 38,836 blocks of 16 tokens hold 64 x 8,704
    blocks = cell.traffic["engine"]["kv_pool_bytes"] // (
        family.kv_bytes_per_token(config) * 16)
    assert blocks == 38_836 and blocks * 16 >= 64 * 8704
    whole = dict(config, num_hidden_layers=61, n_routed_experts=384,
                 vocab_size=163840)
    assert 1.02e12 < family.param_count(whole) < 1.04e12


def test_the_roofline_arithmetic(cell):
    family, config = cell.family, cell.config
    # all 12 held experts of 5 layers, bf16
    assert family.expert_bytes(config, 1.0) == 5 * 12 * 44_040_192 * 2
    assert family.expert_flops(config, 16) == 2 * 16 * 44_040_192
    attended = 64 * 5400.0
    assert family.mla_decode_bytes(config, attended) == \
        6 * 101_124_096 * 2 + 6912 * attended
    flops = family.mla_decode_flops(config, 64, attended)
    assert flops == 6 * (2.0 * 64 * 101_124_096
                         + 2.0 * 64 * (1024 + 64) * attended)
    # the lower bound counts no routed expert
    always = 4_173_177_728 - 20480 * 7168 - 5 * 12 * 44_040_192
    assert family.decode_step_bytes(config, attended) == \
        always * 2 + 6912 * attended


def test_the_config_file_keeps_the_catalogs_numbers(cell):
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["reduced_from"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 384,
                                   "vocab_size": 163840}
    for key, value in {"hidden_size": 7168, "kv_lora_rank": 512,
                       "q_lora_rank": 1536, "qk_nope_head_dim": 128,
                       "qk_rope_head_dim": 64, "v_head_dim": 128,
                       "moe_intermediate_size": 2048,
                       "intermediate_size": 18432,
                       "num_experts_per_tok": 8,
                       "routed_scaling_factor": 2.827,
                       "rope_theta": 50000}.items():
        assert config[key] == value, key


def test_expert_counters_read_nothing_from_a_program_without_them():
    from benchmark import expert_counters

    assert expert_counters.means("no-such-program") is None
    for name in ("moe_expert_roofline", "mla_decode_roofline",
                 "mla_time_share", "moe_time_share"):
        assert cells.load_reader(name + ".offline")(object()) is None
