"""The benchmark's files for family ``phi4flash``: the program's forward
held to the plain reference at the rehearsal's size, the served path at
the cell's own kind of tolerance with a wrong model failing it, the
family file's arithmetic from the published sizes, the cell's entries
in BENCHMARK.json, and a reading of each of the four readers the family
brings."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, correct
from benchmark.cells import ROOT, load_json
from benchmark.reduce import program
from benchmark.reduce.xplane import DeviceTrace, Trace
from ray_tpu._private.scopes import instruction_key

CELL = "phi4-mini-flash.serve-offline-cot"
NEW = ("cross_attn_time_share.offline", "gmu_time_share.offline",
       "shared_kv_decode_roofline.offline",
       "cross_decoder_prefill_time_share.offline")
#: the lists of accepted metrics the cell's name was appended to
JOINED = ("slot_occupancy.offline", "decode_step_p50_ms.offline",
          "decode_hbm_roofline.offline", "compiles_in_window.offline",
          "device_idle_share.offline", "kv_pool_time_share.offline",
          "unscoped_time_share.offline", "engine_host_ms_per_step.offline",
          "idle_attributed_share.offline", "ssm_time_share.offline",
          "ssm_state_time_share.offline", "ssm_decode_roofline.offline",
          "prefill_device_ms_per_ktoken.offline",
          "decode_rows_stalled_share.offline",
          "prefill_queued_p50_ms.offline",
          "fence_return_lag_p50_ms.offline")
#: the cell's tolerance (``families/phi4flash.py logit_tie_tol``) stands
#: between what its engine leaves and what a lower precision leaves at
#: the published widths.  At the rehearsal's width of 64 the tied
#: embedding outweighs what twelve layers add and every answer repeats
#: its last token, under any weights; so the same construction is made
#: anew here over weights whose projections into the residual are 24
#: times the draw's (the layers then weigh as they do at the published
#: width), answers of 48 tokens over seeds 1 to 12: the bf16 program's
#: largest gap 0 to 0.0047, with weights rounded to fp8 0.020 to 0.059
#: (35 to 43 of 48 tokens the reference's argmax)
NANO_TIE_TOL = 0.012


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def _loud(tree):
    def scaled(path, a):
        name = jax.tree_util.keystr(path)
        if any(n in name for n in ("'w2'", "'out_proj'", "'wo'", "'w_out'")):
            return a * 24
        if "'in_proj'" in name:
            return a * 4
        return a * 8 if "'x_proj'" in name else a

    return jax.tree_util.tree_map_with_path(scaled, tree)


@pytest.fixture(scope="module")
def tiny(cell):
    """The rehearsal configuration's program, float32 and bf16, over
    one set of weights."""
    config = load_json(cells.tree(ROOT, "rehearsal", "phi4flash.json"))
    family = cell.family
    prog = family.program(config, {"dtype": jnp.float32, "max_seq": 128})
    bf16 = family.program(config, {"max_seq": 128})
    params = _loud(prog.init(jax.random.PRNGKey(3)))
    return config, family, cell.reference, prog, bf16, params


def _tokens(seed, *shape):
    return np.asarray(jax.random.randint(jax.random.PRNGKey(seed), shape,
                                         0, 512), np.int32)


def test_the_cells_files_are_found_by_name(cell):
    assert cell.config["program"] == {"family": "phi4flash",
                                      "preset": "phi4-mini-flash"}
    assert cell.family.REFERENCE == "phi4flash" and cell.chips == 1
    assert cell.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | set(JOINED) <= names
    for name in names:
        assert callable(cells.load_reader(name))
    for stated in ("sizes", "program", "param_count",
                   "train_flops_per_token", "decode_step_bytes",
                   "kv_bytes_per_token", "attention_shape",
                   "aot_serve_programs", "reference_kwargs",
                   "logit_tie_tol", "window_bytes_per_slot",
                   "state_bytes_per_slot", "ssm_decode_bytes",
                   "shared_kv_decode_bytes"):
        assert callable(getattr(cell.family, stated)), stated


def test_the_cell_exists_only_through_its_entries():
    """PR 41's trap: files under ``benchmark/`` add no cell.  The
    configuration, the cell (one chip) and its four metrics are entries
    of BENCHMARK.json, each new metric listing this cell."""
    bench = cells.load_benchmark()
    config = [c for c in bench["configs"] if c["name"] == "phi4-mini-flash"]
    assert config == [dict(
        config[0], file="benchmark/configs/phi4-mini-flash.json", reduced=[],
        source="https://huggingface.co/microsoft/"
        "Phi-4-mini-flash-reasoning/blob/main/config.json")]
    workload = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload and workload[0]["chips"] == 1
    assert workload[0]["config"] == "phi4-mini-flash"
    assert workload[0]["traffic"] == "serve-offline-cot"
    for name in NEW:
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry and CELL in entry[0]["workloads"]
        assert entry[0]["moves"] == "serve_out_tokens_per_s"
        assert entry[0]["source"] == "device_trace"
        assert entry[0]["unit"] == "%"
    rate = [m for m in bench["end_to_end"]
            if m["name"] == "serve_out_tokens_per_s"]
    assert rate and CELL in rate[0]["workloads"]
    for name in JOINED:
        entry = [m for m in bench["per_layer"] if m["name"] == name]
        assert entry and CELL in entry[0]["workloads"], name


def test_the_traffic_is_the_issues(cell):
    t = cell.traffic
    assert t["clients"] == t["engine"]["max_slots"] == 64
    assert t["prompts"]["tail"] == {"dist": "uniform", "lo": 512,
                                    "hi": 4096}
    assert t["prompts"]["prefix_groups"] == 0
    assert t["prompts"]["p_shared"] == 0.0
    assert t["prompts"]["shape_seed"] == 20261003
    assert t["engine"]["max_new_tokens"] == 768
    assert t["engine"]["kv_block_size"] == 16
    assert t["engine"]["prefill_bucket"] == 512
    assert t["engine"]["param_dtype"] == "bfloat16"
    assert t["engine"]["kv_pool_bytes"] == 65 * 4864 * 5120 \
        == 1_618_739_200
    assert t["config_overrides"] == {"max_seq": 4864}
    assert 4096 + 768 == 4864 and 4864 % 256 == 0     # the band's tile
    assert t["client_lists"] == "file" and t["turns_per_client"] == 12
    assert t["window_requests"] in (64, 96, 128) and t["drain_s"] == 20
    assert t["first_send_spread_s"] == round(t["first_send_spread_s"])
    lo, hi = t["trace_window_s"]
    assert 5.5 <= hi - lo <= 6.5 and hi < 44


def _forward(cfg, params, tokens):
    from ray_tpu.models.phi4flash import phi4flash_forward

    return np.asarray(jax.jit(lambda p, t: phi4flash_forward(p, t, cfg))(
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
    from ray_tpu.models.phi4flash_decode import phi4flash_generate

    config, family, reference, _, bf16, params = tiny
    if "fn" not in _GENERATE:
        _GENERATE["fn"] = jax.jit(lambda p, t: phi4flash_generate(
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
    assert family.mamba_mixer_params(config) == 41_241_600
    assert family.self_attention_params(config) == 19_668_864
    assert family.cross_attention_params(config) == 13_112_704
    assert family.gmu_params(config) == 26_214_400
    assert family.layer_counts(config) == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert family.pool_readers(config) == 8
    assert family.param_count(config) == 3_852_562_944       # "3.8B"
    s = family.sizes(config)
    assert (s["d_model"], s["n_head"], s["n_kv_head"], s["d_ff"]) \
        == (2560, 40, 20, 10240)
    assert (s["d_state"], s["d_conv"], s["expand"], s["dt_rank"]) \
        == (16, 4, 2, 160)
    assert (s["n_layer"], s["window"], s["mb_per_layer"], s["ln_eps"]) \
        == (32, 512, 2, 1e-5)
    assert s["vocab_size"] == 200_064 and s["max_seq"] == 262_144
    assert family.attention_shape(config) == {
        "n_head": 40, "n_kv_head": 20, "head_dim": 64, "n_layer": 1,
        "d_model": 2560}
    assert family.reference_kwargs(config) == {
        "n_head": 40, "n_kv_head": 20, "window": 512, "eps": 1e-5}
    assert family.train_flops_per_token(config, 1024) \
        > 6.0 * family.param_count(config)


def test_the_program_holds_what_the_family_counts(cell):
    from ray_tpu.models.phi4flash import (phi4flash_init,
                                          phi4flash_param_count)

    prog = cell.family.program(cell.config, {})
    assert phi4flash_param_count(prog.cfg) == cell.family.param_count(
        cell.config)
    tree = jax.eval_shape(lambda: phi4flash_init(jax.random.PRNGKey(0),
                                                 prog.cfg))
    assert sum(a.size for a in jax.tree.leaves(tree)) == 3_852_562_944
    assert tree["wte"].shape == (200_064, 2560)          # nothing padded
    assert tree["self"]["mamba"]["mixer"]["in_proj"].shape \
        == (8, 2560, 10_240)
    assert tree["self"]["window"]["attn"]["wkv"].shape == (8, 2560, 2560)
    assert tree["memory"]["mixer"]["A_log"].shape == (16, 5120)
    assert "dt_norm" not in tree["memory"]["mixer"]      # Mamba-1, plain
    assert tree["full"]["attn"]["wq"].shape == (2560, 2560)
    assert tree["cross"]["gmu"]["gmu"]["w_in"].shape == (7, 2560, 5120)
    assert "wkv" not in tree["cross"]["attn"]["attn"]    # no W_k, W_v
    assert tree["cross"]["attn"]["attn"]["subln"].shape == (7, 128)
    assert tree["full"]["mlp"]["w1"].shape == (2560, 20_480)


def test_the_cache_arithmetic(cell):
    from ray_tpu.models import decode_common as dc
    from ray_tpu.models.phi4flash_decode import phi4flash_init_paged_cache

    family, config = cell.family, cell.config
    # ONE layer in the pool: K and V of 20 heads of 64 in bf16
    assert family.kv_bytes_per_token(config) == 5120
    assert family.state_bytes_per_slot(config) == 9 * (
        5120 * 16 * 4 + 3 * 5120 * 2) == 3_225_600
    assert family.window_bytes_per_slot(config) == 8 * 512 * 5120 \
        == 20_971_520
    blocks = cell.traffic["engine"]["kv_pool_bytes"] // (5120 * 16)
    # 64 sequences of the cell's longest (4,864) and one of headroom
    assert blocks == 19_760 and blocks * 16 == 65 * 4864
    prog = family.program(config, {"max_seq": 4864})
    assert prog.cfg.state_bytes_per_slot == 24_197_120
    cache = jax.eval_shape(lambda: phi4flash_init_paged_cache(
        prog.cfg, 64, num_blocks=blocks, block_size=16))

    def nbytes(*names):
        return sum(int(np.prod(cache[n].shape)) * cache[n].dtype.itemsize
                   for n in names)

    # every slot's state, windows and rings, and the snapshot pool's:
    # a slot holds all four of decode_common's state tensors
    assert set(dc._STATE) <= set(cache)
    state = nbytes(*dc._STATE, *("snap_" + n for n in dc._STATE))
    assert state == 2 * 64 * 24_197_120
    assert nbytes("k", "v") == blocks * 16 * 5120
    assert cache["k"].shape == (1, 19_760, 16, 1280)
    assert cache["ssm"].dtype == jnp.float32
    held = 3_852_562_944 * 2 + state + nbytes("k", "v")
    assert 0.77 < held / 16e9 < 0.78            # of the chip, before temps


def test_the_roofline_arithmetic(cell):
    family, config = cell.family, cell.config
    weights = 3_852_562_944 * 2
    # eight layers read each attended position of the one pool
    assert family.decode_step_bytes(config, 64 * 2700.0) == \
        weights + 8 * 5120 * 64 * 2700.0
    assert family.ssm_decode_bytes(config, 64) == \
        9 * 41_241_600 * 2 + 64 * 2 * 3_225_600
    assert family.shared_kv_decode_bytes(config, [100, 5000]) == \
        (19_668_864 + 7 * 13_112_704) * 2 + 8 * 5120 * 5100


def test_the_config_file_keeps_the_catalogs_numbers(cell):
    config = cell.config
    assert config["reduced"] == []
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert len(published) == 17
    for key, value in published.items():
        assert config[key] == value, key
    assert config["source"] == "https://huggingface.co/microsoft/" \
        "Phi-4-mini-flash-reasoning/blob/main/config.json"
    for reason in ("layer_order", "mamba", "memory",
                   "differential_attention", "biases", "positions",
                   "window_edge", "head_dim", "mlp", "state", "cache",
                   "compute_dtype", "param_dtype_serve", "weights",
                   "context"):
        assert config["assumed"][reason], reason
    assert "2507.06607" in config["assumed"]["layer_order"]
    assert "2410.05258" in config["assumed"]["differential_attention"]
    assert config["assumed"]["mamba"]["source"]
    assert "embd_pdrop" in config["keys_ignored"]
    assert "nothing cut" in config["deployment"]
    assert "No train cell" in config["deployment"]


# -- the four readers ---------------------------------------------------------

US = 1000.0
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _op(name, start_us, dur_us):
    return (f"%{name} = bf16[64,2560]{{1,0}} fusion(%x)", start_us * US,
            dur_us * US)


def _run(cell, scoped=True):
    """Two decode waves of 100 us (30 under ``attn_cross``, 10 under
    ``gmu``, 5 under ``attn_full``, 5 under ``kv_pool``) and a prefill
    of 1,000 us (4 under ``attn_cross``, 1 under ``gmu``)."""
    decode, prefill = "jit_pool_step", "jit_paged_prefill_sample"
    modules = [(decode + "(1)", 0.0, 100 * US),
               (decode + "(1)", 200 * US, 100 * US),
               (prefill + "(2)", 400 * US, 1000 * US)]
    ops = []
    for base in (0, 200):
        ops += [_op("fusion.1", base, 30), _op("fusion.2", base + 30, 10),
                _op("fusion.3", base + 40, 5), _op("fusion.4", base + 45, 5),
                _op("fusion.5", base + 50, 50)]
    ops += [_op("fusion.11", 400, 4), _op("fusion.12", 404, 1),
            _op("fusion.13", 405, 995)]
    trace = Trace([DeviceTrace("/device:TPU:0", ops, modules)], [], 0.0,
                  2000 * US)
    key = instruction_key(ops[0][0])
    names = {decode: {"fusion.1": "attn_cross", "fusion.2": "gmu",
                      "fusion.3": "attn_full", "fusion.4": "kv_pool",
                      "fusion.5": "mlp"},
             prefill: {"fusion.11": "attn_cross", "fusion.12": "gmu",
                       "fusion.13": "ssm"}}
    if not scoped:
        names = {p: {n: "mlp" for n in m} for p, m in names.items()}
    maps = {p: {n: {key: s} for n, s in m.items()}
            for p, m in names.items()}
    # two rows a wave: stamps on the host's clock inside (t0, t1)
    rows = [{"prompt_len": 3000, "token_ts": [0.5, 1.0, 2.0]},
            {"prompt_len": 1000, "token_ts": [0.6, 1.0, 2.0]}]
    run = types.SimpleNamespace(
        trace=trace, rows=rows, t0=0.0, t1=3.0,
        ctx=types.SimpleNamespace(cell=cell, peaks=PEAKS),
        engine=types.SimpleNamespace(max_slots=64))
    return run, maps


@pytest.fixture
def readings(cell, monkeypatch):
    def read(scoped=True, of=cell):
        run, maps = _run(of, scoped)
        monkeypatch.setattr(program, "_registry_maps", lambda: maps)
        return {name: cells.load_reader(name)(run) for name in NEW}

    return read


def test_the_four_readers_read_the_new_scopes(cell, readings):
    got = readings()
    family, config = cell.family, cell.config
    # of 2 x 100 + 1,000 us of the two programs
    assert got[NEW[0]] == pytest.approx(100.0 * (60 + 4) / 1200)
    assert got[NEW[1]] == pytest.approx(100.0 * (20 + 1) / 1200)
    # 40 us a step under the three scopes; the waves' contexts are
    # (3001, 1001) and (3002, 1002)
    least = (family.shared_kv_decode_bytes(config, [3001, 1001])
             + family.shared_kv_decode_bytes(config, [3002, 1002])) / 2
    assert got[NEW[2]] == pytest.approx(100.0 * least / 819e9 / 40e-6)
    # of the prefill program's 1,000 us alone
    assert got[NEW[3]] == pytest.approx(100.0 * 5 / 1000)


def test_a_run_without_the_scopes_reads_nothing(cell, readings):
    """The parent's programs have neither scope: the readers hand back
    None, and the line leaves the metrics out."""
    assert readings(scoped=False) == dict.fromkeys(NEW)
    for name in NEW:
        assert cells.load_reader(name)(object()) is None


def test_a_family_without_a_shared_pool_reads_no_roofline(readings):
    other = cells.load_cell("laguna-xs2.serve-offline-mixed")
    assert readings(of=other)[NEW[2]] is None
