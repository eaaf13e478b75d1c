"""The benchmark's files for family ``solar_open2``: the program's
forward held to the plain reference at the rehearsal's size, the served
path at the cell's own kind of tolerance with a wrong model failing it,
the family file's arithmetic from the published sizes, the cell's
entries in BENCHMARK.json, and a reading of each of the four readers
the family brings."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, correct
from benchmark.cells import ROOT, load_json
from benchmark.reduce import launches, program
from benchmark.reduce.xplane import DeviceTrace, Trace
from ray_tpu._private.scopes import instruction_key

CELL = "solar-open2.serve-offline-summarize"
NEW = ("linear_attn_time_share.offline", "linear_state_time_share.offline",
       "linear_attn_decode_roofline.offline",
       "linear_attn_prefill_roofline.offline")
#: the cell's tolerance (``families/solar_open2.py logit_tie_tol``)
#: stands between what its engine leaves and what a lower precision
#: leaves at the published widths.  The rehearsal widths' logits are
#: flatter (std 0.16), so the same construction is made anew from the
#: same two readings here, answers of 48 tokens over seeds 1 to 12: the
#: bf16 program's largest gap 0 to 0.0021, with weights rounded to fp8
#: 0.0068 to 0.023 (41 to 46 of 48 tokens the reference's argmax; at
#: this width the embedding of N(0, 0.1) outweighs what the layers add,
#: so a rounded layer moves a logit less than at the published one)
NANO_TIE_TOL = 0.004


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    """The rehearsal configuration's program, float32 and bf16, over
    one set of weights."""
    config = load_json(cells.tree(ROOT, "rehearsal", "solar_open2.json"))
    family = cell.family
    prog = family.program(config, {"dtype": jnp.float32, "max_seq": 128})
    bf16 = family.program(config, {"max_seq": 128})
    params = prog.init(jax.random.PRNGKey(3))
    return config, family, cell.reference, prog, bf16, params


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


def test_the_cells_files_are_found_by_name(cell):
    assert cell.config["program"] == {"family": "solar_open2",
                                      "preset": "solar-open2"}
    assert cell.family.REFERENCE == "solar_open2" and cell.chips == 1
    assert cell.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {
        "moe_time_share.offline", "moe_expert_roofline.offline",
        "expert_load_max_over_mean.offline", "kv_pool_time_share.offline",
        "unscoped_time_share.offline",
        "prefill_device_ms_per_ktoken.offline",
        "decode_step_p50_ms.offline"} <= names
    assert callable(cell.family.attn_decode_bytes)
    for name in names:
        assert callable(cells.load_reader(name))


def test_the_cell_exists_only_through_its_entries():
    """PR 41's trap: files under ``benchmark/`` add no cell.  The
    configuration, the cell (one chip) and its four metrics are entries
    of BENCHMARK.json, each new metric listing this cell."""
    bench = cells.load_benchmark()
    config = [c for c in bench["configs"] if c["name"] == "solar-open2"]
    assert config == [dict(
        config[0], file="benchmark/configs/solar-open2.json",
        source="https://huggingface.co/upstage/Solar-Open2-250B/blob/main/"
        "config.json",
        reduced=["num_hidden_layers", "n_routed_experts", "vocab_size"])]
    workload = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload and workload[0]["chips"] == 1
    assert workload[0]["traffic"] == "serve-offline-summarize"
    for name in NEW:
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry and CELL in entry[0]["workloads"]
        assert entry[0]["moves"] == "serve_out_tokens_per_s"
        assert entry[0]["source"] == "device_trace"


def test_the_traffic_is_the_issues(cell):
    t = cell.traffic
    assert t["clients"] == t["engine"]["max_slots"] == 64
    assert t["prompts"]["tail"] == {"dist": "uniform", "lo": 2048,
                                    "hi": 8192}
    assert t["prompts"]["prefix_groups"] == 0
    assert t["prompts"]["p_shared"] == 0.0
    assert t["prompts"]["shape_seed"] == 20261002
    assert t["engine"]["max_new_tokens"] == 512
    assert t["engine"]["kv_block_size"] == 16
    assert t["engine"]["prefill_bucket"] == 1024
    assert t["engine"]["param_dtype"] == "bfloat16"
    assert t["config_overrides"] == {"max_seq": 8704}
    assert t["client_lists"] == "file" and t["turns_per_client"] == 12
    assert t["window_requests"] in (64, 96, 128) and t["drain_s"] == 20
    assert t["first_send_spread_s"] == round(t["first_send_spread_s"])
    # the Kimi cell's lengths on purpose: one traffic, two mechanisms
    kimi = load_json(cells.tree(ROOT, "traffic",
                                "serve-offline-codegen.json"))
    assert t["prompts"]["tail"] == kimi["prompts"]["tail"]
    assert t["engine"]["max_new_tokens"] == kimi["engine"]["max_new_tokens"]


def _forward(cfg, params, tokens):
    from ray_tpu.models.solar_open2 import solar_open2_forward

    return np.asarray(jax.jit(lambda p, t: solar_open2_forward(p, t, cfg))(
        params, jnp.asarray(tokens)))[..., :cfg.vocab_size]


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
    from ray_tpu.models.solar_open2_decode import solar_open2_generate

    config, family, reference, _, bf16, params = tiny
    if "fn" not in _GENERATE:
        _GENERATE["fn"] = jax.jit(lambda p, t: solar_open2_generate(
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


def test_fp8_weights_fail_the_cells_tolerance(tiny):
    """Weights rounded to fp8 (the nearest precision below the bf16 the
    configuration states) answer otherwise than the reference over the
    true weights, by more than the tolerance, on every seed."""
    broken = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, tiny[-1])
    results = [_greedy_check(tiny, broken, seed) for seed in (1, 2, 3, 4, 5)]
    assert not any(r["ok"] for r in results), results


def test_the_familys_arithmetic_is_the_published_models(cell):
    family, config = cell.family, cell.config
    assert family.gqa_params(config) == 109_051_904
    assert family.kda_params(config) == 137_732_288
    assert family.kda_matmul_params(config) == 137_625_600
    assert family.expert_params(config) == 3 * 4096 * 1280 == 15_728_640
    assert family.layer_types(config) == ["gqa", "kda", "kda", "kda"]
    assert family.layer_params(config) == [755_245_376] + [783_925_760] * 3
    assert family.param_count(config) == 3_308_353_344
    s = family.sizes(config)
    assert (s["d_model"], s["n_head"], s["n_kv_head"], s["head_dim"]) \
        == (4096, 64, 8, 128)
    assert (s["kda_heads"], s["kda_head_dim"], s["d_conv"],
            s["gate_rank"], s["neg_eigval"]) == (64, 128, 4, 128, True)
    assert (s["d_expert"], s["n_routed"], s["top_k"], s["n_shared"],
            s["route_scale"], len(s["held"])) == (1280, 320, 8, 1, 1.0, 40)
    assert s["vocab_size"] == 24_576 and s["gqa_layers"][:3] == (0, 4, 8)
    assert family.attention_shape(config) == {
        "n_head": 64, "n_kv_head": 8, "head_dim": 128, "n_layer": 1,
        "d_model": 4096}
    # the whole published model: 250 B parameters
    whole = dict(config, num_hidden_layers=48, n_routed_experts=320,
                 vocab_size=196608)
    assert 2.4e11 < family.param_count(whole) < 2.6e11
    # a token's: everything but the experts it does not choose
    active = family.param_count(whole) - 48 * (320 - 8) * 15_728_640
    assert 1.4e10 < active < 1.6e10


def test_the_program_holds_what_the_family_counts(cell):
    from ray_tpu.models.solar_open2 import (solar_open2_init,
                                            solar_open2_param_count)

    prog = cell.family.program(cell.config, {})
    assert solar_open2_param_count(prog.cfg) == cell.family.param_count(
        cell.config)
    tree = jax.eval_shape(lambda: solar_open2_init(jax.random.PRNGKey(0),
                                                   prog.cfg))
    assert sum(a.size for a in jax.tree.leaves(tree)) == 3_308_353_344
    assert prog.cfg.layer_types == ("gqa", "kda", "kda", "kda")
    experts = tree["layers"][1]["moe"]["experts"]
    assert experts["w_gate"].shape == (40, 4096, 1280)      # the share
    assert tree["layers"][1]["moe"]["router"]["w"].shape == (4096, 320)
    assert tree["layers"][0]["attn"]["wg"].shape == (4096, 64, 128)
    assert tree["layers"][2]["kda"]["wqkv"].shape == (4096, 3, 64, 128)
    assert tree["head"].shape == tree["wte"].shape == (24_576, 4096)


def test_the_cache_arithmetic(cell):
    from ray_tpu.models import decode_common as dc
    from ray_tpu.models.solar_open2_decode import (
        solar_open2_init_paged_cache)

    family, config = cell.family, cell.config
    # ONE layer in the pool: K and V of 8 heads of 128 in bf16
    assert family.kv_bytes_per_token(config) == 4096
    assert family.state_bytes_per_slot(config) == 3 * (
        64 * 128 * 128 * 4 + 3 * 24_576 * 2) == 13_025_280
    blocks = cell.traffic["engine"]["kv_pool_bytes"] // (4096 * 16)
    # 64 sequences of the cell's longest (8,704) and one of headroom
    assert blocks == 40_960 and blocks * 16 >= 65 * 8704
    prog = family.program(config, {"max_seq": 8704})
    cache = jax.eval_shape(lambda: solar_open2_init_paged_cache(
        prog.cfg, 64, num_blocks=blocks, block_size=16))
    def nbytes(*names):
        return sum(int(np.prod(cache[n].shape)) * cache[n].dtype.itemsize
                   for n in names)

    # every slot's matrices and windows, and the snapshot pool's
    state = nbytes("conv", "ssm", "snap_conv", "snap_ssm")
    assert state == 2 * 64 * 13_025_280
    assert nbytes("k", "v") == blocks * 16 * 4096
    assert cache["ssm"].dtype == jnp.float32
    assert dc._TENSORS["ssm"][0] == 1 and dc._TENSORS["conv"][0] == 2
    held = 3_308_353_344 * 2 + state + nbytes("k", "v")
    assert 0.66 < held / 16e9 < 0.69            # of the chip, before temps


def test_the_roofline_arithmetic(cell):
    family, config = cell.family, cell.config
    assert family.expert_bytes(config, 1.0) == 4 * 40 * 15_728_640 * 2
    assert family.expert_flops(config, 64) == 2 * 64 * 15_728_640
    assert family.attn_decode_bytes(config, [100, 5000]) == \
        109_051_904 * 2 + 4096 * 5100
    # a wave of 64 rows: three layers' weights once, every row's
    # matrices and windows read and written
    assert family.linear_decode_bytes(config, 64) == \
        3 * 137_732_288 * 2 + 64 * 2 * 13_025_280
    # a token: 2 per matmul parameter and 6 x 128 x 128 a head, a layer
    assert family.linear_prefill_flops(config, 1000) == 1000 * 3 * (
        2 * 137_625_600 + 6 * 128 * 128 * 64)
    attended = 64 * 5400.0
    always = 3_308_353_344 - 24_576 * 4096 - 4 * 40 * 15_728_640
    assert family.decode_step_bytes(config, attended) == \
        always * 2 + 4096 * attended


def test_the_config_file_keeps_the_catalogs_numbers(cell):
    config = cell.config
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["reduced_from"] == {"num_hidden_layers": 48,
                                      "n_routed_experts": 320,
                                      "vocab_size": 196608}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 40, 24576)
    for key, value in {
            "hidden_size": 4096, "num_attention_heads": 64,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 10240, "moe_intermediate_size": 1280,
            "num_experts_per_tok": 8, "n_shared_experts": 1,
            "routed_scaling_factor": 1, "norm_topk_prob": True,
            "rms_norm_eps": 1e-05, "use_rope": False, "use_gqa_gate": True,
            "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
            "first_k_dense_replace": 0, "gqa_interval": 3,
            "max_position_embeddings": 1048576, "rope_theta": 10000,
            "partial_rotary_factor": 1, "tie_word_embeddings": False,
            "model_type": "solar_open2"}.items():
        assert config[key] == value, key
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    # the list stays whole, as published: the family cuts it
    assert config["gqa_layers"] == list(range(0, 48, 4))
    for reason in ("cut", "experts_held", "expert_load", "use_gqa_gate",
                   "linear_attn", "kda_use_full_proj",
                   "kda_allow_neg_eigval", "decay", "router",
                   "shared_expert", "weights", "compute_dtype",
                   "param_dtype_serve", "cache", "context", "keys_ignored"):
        assert config["assumed"][reason], reason
    assert "EP8" in config["deployment"]
    assert "no train cell" in config["deployment"]


# -- the four readers ---------------------------------------------------------

US = 1000.0
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _op(name, start_us, dur_us):
    return (f"%{name} = bf16[64,4096]{{1,0}} fusion(%x)", start_us * US,
            dur_us * US)


def _run(cell, scoped=True):
    """Two decode waves of 100 us (40 under ``attn_linear``, 10 under
    ``linear_state``), a prefill of 3,000 tokens the window holds whole
    (1,000 us: 600 and 100) and one its end cuts (400 us of
    ``attn_linear``)."""
    decode, prefill = "jit_pool_step", "jit_paged_prefill_sample"
    modules = [(decode + "(1)", 0.0, 100 * US),
               (decode + "(1)", 200 * US, 100 * US),
               (prefill + "(2)", 400 * US, 1000 * US),
               (prefill + "(2)", 1600 * US, 400 * US)]
    ops = []
    for base in (0, 200):
        ops += [_op("fusion.1", base, 40), _op("fusion.2", base + 40, 10),
                _op("fusion.3", base + 50, 50)]
    ops += [_op("fusion.11", 400, 600), _op("fusion.12", 1000, 100),
            _op("fusion.13", 1100, 300), _op("fusion.11", 1600, 400)]
    trace = Trace([DeviceTrace("/device:TPU:0", ops, modules)], [], 0.0,
                  2000 * US)
    key = instruction_key(ops[0][0])
    names = {decode: {"fusion.1": "attn_linear", "fusion.2": "linear_state",
                      "fusion.3": "mlp"},
             prefill: {"fusion.11": "attn_linear",
                       "fusion.12": "linear_state",
                       "fusion.13": "moe_experts"}}
    if not scoped:
        names = {p: {n: "mlp" for n in m} for p, m in names.items()}
    maps = {p: {n: {key: s} for n, s in m.items()}
            for p, m in names.items()}

    def pair(start, end, whole, n_tail):
        return launches.Pair(
            {"kind": "prefill", "program": prefill, "n_tail": n_tail},
            start * US, end * US, whole, False, not whole, None, 0.0, 0.0,
            None, None)

    joined = launches.Joined(
        [pair(400, 1400, True, 3000), pair(1600, 2000, False, 5000)], 2,
        "mark", None, None, 0.0, 2000 * US)
    # two rows a wave: stamps on the host's clock inside (t0, t1)
    rows = [{"prompt_len": 3000, "token_ts": [0.5, 1.0, 2.0]},
            {"prompt_len": 5000, "token_ts": [0.6, 1.0, 2.0]}]
    run = types.SimpleNamespace(
        trace=trace, rows=rows, t0=0.0, t1=3.0,
        ctx=types.SimpleNamespace(cell=cell, peaks=PEAKS),
        engine=types.SimpleNamespace(max_slots=64))
    run._program_reduce = {"launches": joined}
    return run, maps


@pytest.fixture
def readings(cell, monkeypatch):
    def read(scoped=True):
        run, maps = _run(cell, scoped)
        monkeypatch.setattr(program, "_registry_maps", lambda: maps)
        return {name: cells.load_reader(name)(run) for name in NEW}

    return read


def test_the_four_readers_read_the_new_scopes(cell, readings):
    got = readings()
    family, config = cell.family, cell.config
    # of 2 x 100 + 1,000 + 400 us of the two programs
    assert got[NEW[0]] == pytest.approx(100.0 * (80 + 600 + 400) / 1600)
    assert got[NEW[1]] == pytest.approx(100.0 * (20 + 100) / 1600)
    # 50 us a step under the two scopes, two rows a wave
    assert got[NEW[2]] == pytest.approx(
        100.0 * family.linear_decode_bytes(config, 2) / 819e9 / 50e-6)
    # the whole prefill alone: 700 us under the two for 3,000 tokens
    assert got[NEW[3]] == pytest.approx(
        100.0 * family.linear_prefill_flops(config, 3000) / 197e12 / 700e-6)


def test_a_run_without_the_scopes_reads_nothing(cell, readings):
    """The parent's programs have neither scope: the readers hand back
    None, and the line leaves the metrics out."""
    assert readings(scoped=False) == dict.fromkeys(NEW)
    for name in NEW:
        assert cells.load_reader(name)(object()) is None


def test_a_family_without_such_layers_reads_nothing(readings, monkeypatch):
    other = cells.load_cell("laguna-xs2.serve-offline-mixed")
    run, maps = _run(other)
    monkeypatch.setattr(program, "_registry_maps", lambda: maps)
    assert cells.load_reader(NEW[2])(run) is None
    assert cells.load_reader(NEW[3])(run) is None
