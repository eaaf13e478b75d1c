"""The benchmark's files for family ``laguna``: the program's forward
held to the plain reference, the served path at the cell's own kind of
tolerance with a wrong model failing it, the family file's arithmetic
from the published sizes, and the cell's entries in BENCHMARK.json."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, correct
from benchmark.cells import ROOT, load_json

CELL = "laguna-xs2.serve-offline-mixed"
#: the cell's tolerance (``families/laguna.py logit_tie_tol``) stands
#: between what its engine leaves and what fp8 weights leave at the
#: published widths.  The rehearsal widths' logits are flatter (std
#: 0.16), so the same construction is made anew from the same two
#: readings here, answers of 48 tokens over seeds 1 to 5: the bf16
#: program's largest gap 0 to 0.0008, with weights rounded to fp8 0.011
#: to 0.026.  (Of seeds 6 to 12, three answers of 48 tokens never meet a
#: near-tie and read 0 under fp8 too: a cell checks 512 tokens an
#: answer.)
NANO_TIE_TOL = 0.003


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    """The rehearsal configuration's program, float32 and bf16, over
    one set of weights."""
    config = load_json(cells.tree(ROOT, "rehearsal", "laguna.json"))
    family = cell.family
    prog = family.program(config, {"dtype": jnp.float32, "max_seq": 128})
    bf16 = family.program(config, {"max_seq": 128})
    params = prog.init(jax.random.PRNGKey(3))
    return config, family, cell.reference, prog, bf16, params


def _forward(cfg, params, tokens):
    from ray_tpu.models.laguna import laguna_forward

    return np.asarray(jax.jit(lambda p, t: laguna_forward(p, t, cfg))(
        params, jnp.asarray(tokens)))[..., :cfg.vocab_size]


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


def test_the_cells_files_are_found_by_name(cell):
    assert cell.config["program"] == {"family": "laguna",
                                      "preset": "laguna-xs2"}
    assert cell.family.REFERENCE == "laguna" and cell.chips == 1
    assert cell.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert {"window_attn_time_share.offline", "full_attn_time_share.offline",
            "attn_decode_roofline.offline", "kv_reserved_share.offline",
            "moe_time_share.offline", "moe_expert_roofline.offline",
            "expert_load_max_over_mean.offline",
            "kv_pool_time_share.offline",
            "unscoped_time_share.offline"} <= names
    assert not {"decode_hbm_roofline.offline", "launch_lag_p50_ms.offline",
                "mla_time_share.offline"} & names
    for name in names:
        assert callable(cells.load_reader(name))


def test_the_cell_exists_only_through_its_entries():
    """PR 41's trap: files under ``benchmark/`` add no cell.  The
    configuration, the cell and its four metrics are entries of
    BENCHMARK.json, each new metric listing this cell alone."""
    bench = cells.load_benchmark()
    config = [c for c in bench["configs"] if c["name"] == "laguna-xs2"]
    assert config == [dict(
        config[0], file="benchmark/configs/laguna-xs2.json",
        source="https://huggingface.co/poolside/Laguna-XS.2/blob/main/"
        "config.json", reduced=["num_hidden_layers"])]
    workload = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload and workload[0]["chips"] == 1
    assert workload[0]["traffic"] == "serve-offline-mixed"
    for name in ("window_attn_time_share.offline",
                 "full_attn_time_share.offline",
                 "attn_decode_roofline.offline",
                 "kv_reserved_share.offline"):
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry and entry[0]["workloads"] == [CELL]
        assert entry[0]["moves"] == "serve_out_tokens_per_s"


def test_the_traffic_is_the_issues(cell):
    t = cell.traffic
    assert t["clients"] == t["engine"]["max_slots"] == 64
    assert t["prompts"]["tail"] == {"dist": "uniform", "lo": 256,
                                    "hi": 8192}
    assert t["prompts"]["prefix_groups"] == 0
    assert t["prompts"]["p_shared"] == 0.0
    assert t["engine"]["max_new_tokens"] == 512
    assert t["engine"]["kv_block_size"] == 16
    assert t["engine"]["prefill_bucket"] == 1024
    assert t["config_overrides"] == {"max_seq": 8704}
    assert t["client_lists"] == "file" and "shape_seed" in t["prompts"]
    assert t["window_requests"] % 64 == 0 and t["turns_per_client"] == 32


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
    got = float(jax.jit(prog.loss)(params, {"tokens": jnp.asarray(toks)}))
    assert abs(got - want) / want < correct.LOSS_RTOL


_GENERATE = {}


def _greedy_check(tiny, params_for_engine, seed):
    """The program's bf16 greedy continuation of a prompt, teacher
    forced through the float32 reference over the TRUE weights: what
    the harness's `correct` does to a served answer."""
    from ray_tpu.models.laguna_decode import laguna_generate

    config, family, reference, _, bf16, params = tiny
    if "fn" not in _GENERATE:
        _GENERATE["fn"] = jax.jit(lambda p, t: laguna_generate(
            p, t, bf16.cfg, max_new_tokens=48, temperature=0.0))
    prompt = _tokens(seed, 1, 24)
    out = np.asarray(_GENERATE["fn"](params_for_engine,
                                     jnp.asarray(prompt)))[0]
    lg = correct.reference_generated_logits(
        reference, params, out, 24, vocab_size=bf16.cfg.vocab_size,
        max_seq=bf16.cfg.max_seq, **family.reference_kwargs(config))
    return correct.check_greedy(lg, out[24:], NANO_TIE_TOL)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5])
def test_the_bf16_program_passes_the_cells_tolerance(tiny, seed):
    res = _greedy_check(tiny, tiny[-1], seed)
    assert res["ok"], res


def _fp8(params):
    return jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)


def test_fp8_weights_fail_the_cells_tolerance(tiny):
    """Weights rounded to fp8 answer otherwise than the reference over
    the true weights, by more than the tolerance, on every seed."""
    broken = _fp8(tiny[-1])
    results = [_greedy_check(tiny, broken, seed) for seed in (1, 2, 3, 4, 5)]
    assert not any(r["ok"] for r in results), results


def test_the_familys_arithmetic_is_the_published_models(cell):
    family, config = cell.family, cell.config
    assert family.expert_params(config) == 3 * 2048 * 512 == 3_145_728
    assert family.layer_params(config) == [
        79_794_176, 846_860_544, 846_860_544, 846_860_544, 838_439_168]
    assert family.param_count(config) == 3_869_858_816
    s = family.sizes(config)
    assert s["layer_types"] == ("full", "window", "window", "window", "full")
    assert s["heads_per_layer"] == (48, 64, 64, 64, 48)
    assert s["mlp_types"] == ("dense",) + ("sparse",) * 4
    assert (s["d_model"], s["head_dim"], s["n_kv_head"], s["window"]) \
        == (2048, 128, 8, 512)
    assert (s["d_ff"], s["d_expert"], s["n_routed"], s["top_k"],
            s["n_shared"], s["route_scale"]) == (8192, 512, 256, 8, 1, 2.5)
    assert s["full_rotary_dim"] == 64 and s["vocab_size"] == 100_352
    shape = family.attention_shape(config)
    assert shape == {"n_head": 48, "n_kv_head": 8, "head_dim": 128,
                     "n_layer": 2, "d_model": 2048}
    # the whole published model: 33 B parameters, 3 B of them a token's
    whole = dict(config, num_hidden_layers=40)
    assert 3.3e10 < family.param_count(whole) < 3.4e10


def test_the_program_holds_what_the_family_counts(cell):
    from ray_tpu.models.laguna import laguna_init, laguna_param_count

    prog = cell.family.program(cell.config, {})
    assert laguna_param_count(prog.cfg) == cell.family.param_count(
        cell.config)
    tree = jax.eval_shape(lambda: laguna_init(jax.random.PRNGKey(0),
                                              prog.cfg))
    assert sum(a.size for a in jax.tree.leaves(tree)) == 3_869_858_816
    experts = tree["layers"][1]["moe"]["experts"]
    assert experts["w_gate"].shape == (256, 2048, 512)      # every one held
    assert tree["layers"][1]["attn"]["wq"].shape == (2048, 64, 128)
    assert tree["layers"][4]["attn"]["wq"].shape == (2048, 48, 128)
    assert tree["head"].shape == tree["wte"].shape == (100_352, 2048)


def test_the_cache_arithmetic_by_layer_type(cell):
    family, config = cell.family, cell.config
    # K and V of 8 heads of 128 in bf16: 4,096 B a token a layer
    assert family.kv_bytes_per_token(config) == 2 * 4096
    assert family.window_bytes_per_slot(config) == 3 * 512 * 4096
    blocks = cell.traffic["engine"]["kv_pool_bytes"] // (
        family.kv_bytes_per_token(config) * 16)
    # 4 GiB of pool hold 524,288 tokens of full-layer rows: 94% of 64
    # requests at the cell's longest (8,704)
    assert blocks == 32_768 and blocks * 16 == 524_288
    assert 0.94 < blocks * 16 / (64 * 8704) < 0.95
    # the mean request reserves (256 + 8192) / 2 + 512 = 4,736 tokens
    held = 8192 * 4736 + family.window_bytes_per_slot(config)
    assert 0.46 < held / (20_480 * 4736) < 0.48


def test_the_roofline_arithmetic(cell):
    family, config = cell.family, cell.config
    assert family.expert_bytes(config, 1.0) == 4 * 256 * 3_145_728 * 2
    assert family.expert_bytes(config, 0.5) == 4 * 128 * 3_145_728 * 2
    assert family.expert_flops(config, 512) == 2 * 512 * 3_145_728
    weights = 2 * (2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48) \
        + 3 * (2 * 2048 * 64 * 128 + 2 * 2048 * 1024 + 2048 * 64)
    # a row of context 100 reads 100 rows of all five layers; one of
    # 5,000 reads 5,000 of the two full layers and 512 of the three
    assert family.attn_decode_bytes(config, [100, 5000]) == weights * 2 \
        + 4096 * (5 * 100 + 2 * 5000 + 3 * 512)
    attended = 64 * 4500.0
    always = 3_869_858_816 - 100_352 * 2048 - 4 * 256 * 3_145_728
    assert family.decode_step_bytes(config, attended) == \
        always * 2 + 8192 * attended


def test_the_config_file_keeps_the_catalogs_numbers(cell):
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["reduced_from"] == {"num_hidden_layers": 40}
    assert config["num_hidden_layers"] == 5
    for key, value in {"hidden_size": 2048, "head_dim": 128,
                       "num_attention_heads": 48,
                       "num_key_value_heads": 8, "sliding_window": 512,
                       "intermediate_size": 8192, "num_experts": 256,
                       "num_experts_per_tok": 8,
                       "moe_intermediate_size": 512,
                       "shared_expert_intermediate_size": 512,
                       "moe_routed_scaling_factor": 2.5,
                       "vocab_size": 100352, "rms_norm_eps": 1e-06,
                       "max_position_embeddings": 262144,
                       "tie_word_embeddings": False, "gating": True}.items():
        assert config[key] == value, key
    # the per-layer lists stay whole, as published
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) \
        == len(config["num_attention_heads_per_layer"]) == 40
    assert config["rope_parameters"]["full_attention"]["factor"] == 64
    for reason in ("cut", "gating", "router", "shared_expert", "qk_norm",
                   "rotary_layout", "yarn", "weights", "compute_dtype",
                   "param_dtype_serve", "cache", "context", "keys_ignored"):
        assert config["assumed"][reason], reason
    assert "pipeline stage" in config["deployment"]


def test_the_new_readers_read_nothing_from_a_program_without_them():
    for name in ("window_attn_time_share", "full_attn_time_share",
                 "attn_decode_roofline"):
        assert cells.load_reader(name + ".offline")(object()) is None


def test_kv_reserved_share_reads_the_registrys_sums(monkeypatch):
    from ray_tpu.util import metrics

    read = cells.load_reader("kv_reserved_share.offline")

    class Registry:
        def __init__(self, dumps):
            self.dumps = dumps

        def snapshot(self):
            return self.dumps

    monkeypatch.setattr(metrics, "_registry", Registry({}))
    assert read(object()) is None
    monkeypatch.setattr(metrics, "_registry", Registry({
        "serve_kv_reach_pool_bytes_total": {"values": [[[], 400.0]]},
        "serve_kv_reach_window_bytes_total": {"values": [[[], 70.0]]},
        "serve_kv_reach_full_bytes_total": {"values": [[[], 1000.0]]}}))
    assert read(object()) == pytest.approx(47.0)
