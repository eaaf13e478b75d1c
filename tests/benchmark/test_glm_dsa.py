"""The benchmark's files for family ``glm_dsa``: the cell's files found
by name and its entries in BENCHMARK.json, the family file's arithmetic
against the program's tree, the configuration against the catalog's
numbers, the served path at the cell's own kind of tolerance with
attention over every position (and three other wrong models) failing
it, and a reading of each of the four readers the family brings."""

import dataclasses
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
from ray_tpu.ops import dsa

CELL = "glm-5.serve-offline-longdoc"
NEW = ("index_time_share.offline", "sparse_attn_decode_roofline.offline",
       "index_prefill_roofline.offline", "index_selected_share.offline")
#: the cell's tolerance (``families/glm_dsa.py logit_tie_tol``) stands
#: between what its engine leaves and what a lower precision or a wrong
#: rule leaves at the published widths.  The same construction is made
#: anew here from the same kind of readings, at the rehearsal's widths
#: with the cache's three as `WIDE` has them (logits of deviation 0.16)
#: and the attention made loud (`_loud`),
#: answers of 48 tokens over seeds 1 to 6: the bf16 program's largest gap
#: 0 to 0.104; attention over EVERY position instead of the selected 24
#: 0.18 to 0.65 (0.33 and more on seeds 1 to 4); weights rounded to fp8
#: 0.16 to 0.45; an indexer without its ReLU 0.24 to 0.92; without its
#: head weights 0.52 to 0.79
NANO_TIE_TOL = 0.135
#: the rehearsal file's cache is forced tiny by the walk's small pool (a
#: latent of 8 + 2 and an index key of 4, 2 heads picking 12, so that 56
#: B a token hold the cell's 12,800 context): there one swapped place of
#: 12 at the selection's edge outweighs a wrong rule.  The tolerance is
#: read at ``nano``'s cache widths instead
WIDE = {"max_seq": 128, "kv_lora_rank": 32, "qk_rope_dim": 8,
        "index_head_dim": 16, "index_n_heads": 4, "index_topk": 24}
SEEDS = (1, 2, 3, 4)
#: deviation of a query's attention logits under `_loud`
SHARPNESS = 2.0


def _loud(params, cfg):
    """The seeded weights with the attention's matrices rescaled until
    the selection is heard: kimi_k2's N(0, 0.02) leaves the attention a
    hundredth of what the FFN adds to the stream and its softmax flat,
    so that attending every position instead of the selected ones moves
    no logit by more than a flipped expert does, at any width.  Here
    ``W_uk``, ``W_uv``, the rotary key's columns of ``W_kva`` and
    ``W_o`` become N(0, 1 / fan-in) (unit keys and values, an output of
    the order of its input) and ``W_qb`` `SHARPNESS` times that.
    (models/glm_dsa.py's docstring has why the program's own draw does
    not do this: at the published depth and widths the edge of a
    selection under seeded weights is then louder than the tolerance.)"""
    c, d, H = cfg.kv_lora_rank, cfg.d_model, cfg.n_head
    drawn, out = 0.02, 0.02 / (2 * cfg.n_layer) ** 0.5     # kimi_k2_init's

    def to(a, std, was=drawn):
        return (a.astype(jnp.float32) * (std / was)).astype(a.dtype)

    def attn(a):
        return dict(
            a, wq_b=to(a["wq_b"], SHARPNESS * cfg.q_lora_rank ** -0.5),
            wk_b=to(a["wk_b"], c ** -0.5), wv_b=to(a["wv_b"], c ** -0.5),
            wkv_a=jnp.concatenate([
                a["wkv_a"][..., :c], to(a["wkv_a"][..., c:], d ** -0.5)],
                axis=-1),
            wo=to(a["wo"], (H * cfg.v_head_dim) ** -0.5, out))

    return {k: dict(v, attn=attn(v["attn"])) if k in ("dense", "moe") else v
            for k, v in params.items()}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def tiny(cell):
    config = load_json(cells.tree(ROOT, "rehearsal", "glm_dsa.json"))
    family = cell.family
    bf16 = family.program(config, WIDE)
    params = _loud(family.program(config, dict(
        WIDE, dtype=jnp.float32)).init(jax.random.PRNGKey(3)), bf16.cfg)
    stated = dict(family.reference_kwargs(config),
                  qk_rope_dim=WIDE["qk_rope_dim"],
                  index_topk=WIDE["index_topk"])
    return config, family, cell.reference, bf16, params, stated


# -- files and entries ---------------------------------------------------------

def test_the_cells_files_are_found_by_name(cell):
    assert cell.config["program"] == {"family": "glm_dsa", "preset": "glm-5"}
    assert cell.family.REFERENCE == "glm_dsa" and cell.chips == 1
    assert cell.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tokens_per_s", "setup_s"}
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) | {"mla_time_share.offline", "moe_time_share.offline",
                       "moe_expert_roofline.offline",
                       "kv_pool_time_share.offline",
                       "unscoped_time_share.offline",
                       "setup_params_s"} <= names
    assert "mla_decode_roofline.offline" not in names
    for name in names:
        assert callable(cells.load_reader(name))
    for attr in ("sparse_decode_bytes", "sparse_decode_flops",
                 "index_prefill_flops", "expert_bytes", "logit_tie_tol"):
        assert callable(getattr(cell.family, attr))
    # a family whose count of positions differs overrides the metric
    assert not hasattr(cell.family, "mla_decode_bytes")


def test_the_cell_exists_only_through_its_entries():
    bench = cells.load_benchmark()
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["config"] == "glm-5"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    kimi = "kimi-k2-code.serve-offline-codegen"
    for m in bench["per_layer"]:
        listed = m.get("workloads", ())
        if m["name"] == "mla_decode_roofline.offline":
            assert CELL not in listed and kimi in listed
        elif kimi in listed and (m["name"].endswith(".offline")
                                 or m["name"].startswith("setup_")):
            assert CELL in listed, m["name"]
        if m["name"] in NEW:
            assert listed == [CELL] and m["moves"] == "serve_out_tokens_per_s"
    assert {m["name"] for m in bench["per_layer"]} >= set(NEW)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["serve_out_tokens_per_s"]["workloads"]


def test_the_traffic_is_the_issues(cell):
    t = cell.traffic
    assert t["clients"] == t["engine"]["max_slots"] == 32
    assert t["prompts"]["tail"] == {"dist": "uniform", "lo": 4096,
                                    "hi": 12288}
    assert t["prompts"]["p_shared"] == 0 and not t["prompts"]["prefix_groups"]
    # no request is short enough to escape the selection
    assert t["prompts"]["tail"]["lo"] > cell.config["index_topk"]
    assert t["engine"]["max_new_tokens"] == 512
    assert t["config_overrides"]["max_seq"] == 12800 \
        >= t["prompts"]["tail"]["hi"] + t["engine"]["max_new_tokens"]
    assert t["engine"]["kv_pool_bytes"] == 3221225472
    assert t["client_lists"] == "file" and t["window_requests"] in (
        32, 48, 64)
    assert t["prompts"]["shape_seed"] == 20261004


# -- arithmetic ---------------------------------------------------------------

def test_the_familys_arithmetic_is_the_programs_tree(cell):
    family, config = cell.family, cell.config
    assert family.mla_params(config) == 165_022_208
    assert family.indexer_params(config) == 9_371_904
    assert family.expert_params(config) == 37_748_736
    per = family.layer_params(config)
    assert per == {"dense": 400_898_816, "expert": 817_708_032}
    assert per["expert"] - 16 * 37_748_736 == 213_728_256
    assert family.layer_counts(config) == {"dense": 1, "expert": 4}
    assert family.param_count(config) == 3_909_632_768
    assert family.kv_bytes_per_token(config) == 7_040
    prog = family.program(config, {"max_seq": 12800,
                                   "param_dtype": jnp.bfloat16})
    tree = jax.eval_shape(prog.init, jax.random.PRNGKey(0))
    leaves = sum(a.size for a in jax.tree.leaves(tree))
    pad = prog.cfg.padded_vocab - prog.cfg.vocab_size
    assert prog.cfg.padded_vocab == 19_456
    assert leaves - 2 * pad * 6144 == 3_909_632_768
    one = lambda stack, key: sum(                      # noqa: E731
        a.size // a.shape[0] for a in jax.tree.leaves(stack[key]))
    assert one(tree["moe"], "attn") == 165_022_208
    assert one(tree["dense"], "indexer") == 9_371_904
    from ray_tpu.models.glm_dsa_decode import glm_dsa_init_paged_cache

    cache = glm_dsa_init_paged_cache(prog.cfg, 2, num_blocks=3,
                                     block_size=16)
    from ray_tpu.models.decode_common import block_bytes, cache_reach

    assert block_bytes(cache) == 16 * 7_040
    assert cache_reach(cache)["pool_bytes_per_token"] == 7_040
    assert {n: cache[n].shape[-1] for n in ("ckv", "kpe", "kidx")} == {
        "ckv": 512, "kpe": 64, "kidx": 128}
    shape = family.attention_shape(config)
    assert shape["qk_head_dim"] == 256 and shape["v_head_dim"] == 256
    assert shape["latent_dim"] == 576 and shape["index_head_dim"] == 128
    assert "16 chips" in config["deployment"]
    assert "no train cell" in config["deployment"]


def test_the_roofline_arithmetic(cell):
    family, config = cell.family, cell.config
    # a wave of 32 rows at a mean context of 8,700: every position's
    # index key, 2,048 positions' latents a row, the weights once
    rows, positions = 32, 32 * 8700
    weights = 5 * (165_022_208 + 9_371_904) * 2
    assert family.sparse_decode_bytes(config, rows, positions) == \
        weights + 5 * (256 * positions + 1152 * 2048 * rows)
    # a context under index_topk reads all it has, and no more
    assert family.sparse_decode_bytes(config, 2, 3000) == \
        weights + 5 * (256 + 1152) * 3000
    assert family.sparse_decode_bytes(config, 2, 9000, 3000) == \
        weights + 5 * (256 * 9000 + 1152 * 3000)
    dense_mla = 5 * 165_022_208 * 2 + 5 * 1152 * positions
    assert family.sparse_decode_bytes(config, rows, positions) < dense_mla
    assert family.sparse_decode_flops(config, rows, positions) == 5 * (
        2.0 * rows * (165_022_208 + 9_371_904)
        + 2.0 * 32 * 128 * positions
        + 2.0 * 64 * (1024 + 64) * 2048 * rows)
    assert family.index_prefill_flops(config, 1000) == 5 * 8192 * 1000
    assert family.expert_bytes(config, 0.5) == 4 * 8 * 37_748_736 * 2
    assert family.decode_step_bytes(config, positions) < \
        family.param_count(config) * 2 + 7040 * positions


def test_the_config_file_keeps_the_catalogs_numbers(cell):
    c = cell.config
    published = {
        "hidden_size": 6144, "num_attention_heads": 64,
        "num_key_value_heads": 64, "head_dim": 64, "q_lora_rank": 2048,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "qk_head_dim": 256, "v_head_dim": 256,
        "index_n_heads": 32, "index_head_dim": 128, "index_topk": 2048,
        "moe_intermediate_size": 2048, "intermediate_size": 12288,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "rms_norm_eps": 1e-05,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
        "max_position_embeddings": 202752, "model_type": "glm_moe_dsa",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "rope_interleave": True, "indexer_rope_interleave": True,
        "attention_bias": False, "ep_size": 1, "moe_layer_freq": 1,
        "hidden_act": "silu"}
    assert {k: c[k] for k in published} == published
    assert c["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                            "n_routed_experts", "vocab_size"]
    assert c["reduced_from"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880}
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["n_routed_experts"], c["vocab_size"]) == (5, 1, 16, 19360)
    for said in ("FP8", "Hadamard", "lower position"):
        assert said in c["assumed"]["indexer"], said
    assert "NOT served" in c["assumed"]["keys_ignored"]
    assert "(2i, 2i+1)" in c["assumed"]["rotary_layout"]
    assert cell.family.sizes(c)["n_routed"] == 256
    assert cell.family.sizes(c)["held"] == tuple(range(16))


# -- the served path at the cell's kind of tolerance --------------------------

_GENERATE = {}


def _without_relu(qi, w, k):
    s = jnp.einsum("...tjd,...sd->...tjs", qi, k.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...tjs,...tj->...ts", s, w.astype(jnp.float32))


def _without_weights(qi, w, k):
    s = jnp.einsum("...tjd,...sd->...tjs", qi, k.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(s), axis=-2)


FAULTS = {"dense_attention": None, "fp8_weights": None,
          "no_relu": _without_relu, "no_head_weights": _without_weights}


def _greedy_check(tiny, fault, seed, monkeypatch):
    """The program's bf16 greedy continuation of a prompt, teacher
    forced through the float32 reference over the TRUE weights: what
    the harness's `correct` does to a served answer."""
    from ray_tpu.models.glm_dsa_decode import glm_dsa_generate

    config, family, reference, bf16, params, stated = tiny
    # attention over every position: a selection no context outgrows
    cfg = dataclasses.replace(bf16.cfg, index_topk=1 << 20) \
        if fault == "dense_attention" else bf16.cfg
    if FAULTS.get(fault) is not None:
        monkeypatch.setattr(dsa, "index_scores", FAULTS[fault])
    if (cfg, fault) not in _GENERATE:
        _GENERATE[cfg, fault] = jax.jit(lambda p, t: glm_dsa_generate(
            p, t, cfg, max_new_tokens=48, temperature=0.0))
    served = jax.tree.map(
        lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params) if fault == "fp8_weights" else params
    prompt = np.asarray(jax.random.randint(
        jax.random.PRNGKey(seed), (1, 24), 0, 512), np.int32)
    out = np.asarray(_GENERATE[cfg, fault](served, jnp.asarray(prompt)))[0]
    lg = correct.reference_generated_logits(
        reference, params, out, 24, vocab_size=cfg.vocab_size,
        max_seq=cfg.max_seq, **stated)
    return correct.check_greedy(lg, out[24:], NANO_TIE_TOL)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_bf16_program_passes_the_cells_tolerance(tiny, seed,
                                                     monkeypatch):
    res = _greedy_check(tiny, "", seed, monkeypatch)
    assert res["ok"], res


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_wrong_model_fails_the_cells_tolerance(tiny, fault, monkeypatch):
    """Attention over every position instead of the selected ones,
    weights rounded to fp8 (the nearest precision below the bf16 the
    configuration states), an indexer without its ReLU or without its
    head weights: each answers otherwise than the reference over the
    true weights, by more than the tolerance, on every seed."""
    results = [_greedy_check(tiny, fault, seed, monkeypatch)
               for seed in SEEDS]
    assert not any(r["ok"] for r in results), results


# -- the four readers this family brings --------------------------------------

US = 1000.0
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _op(name, start_us, dur_us):
    return (f"%{name} = bf16[64,4096]{{1,0}} fusion(%x)", start_us * US,
            dur_us * US)


def _run(cell, scoped=True):
    """Two decode waves of 10,000 us (3,000 under ``attn_index``, 2,000
    under ``mla``, 1,000 under ``kv_pool``), a prefill of 6,000 tokens
    behind 2,000 resident ones the window holds whole (500,000 us,
    120,000 of them under ``attn_index``) and one its end cuts."""
    decode, prefill = "jit_pool_step", "jit_paged_prefill_sample"
    modules = [(decode + "(1)", 0.0, 10000 * US),
               (decode + "(1)", 20000 * US, 10000 * US),
               (prefill + "(2)", 40000 * US, 500000 * US),
               (prefill + "(2)", 600000 * US, 40000 * US)]
    ops = []
    for base in (0, 20000):
        ops += [_op("fusion.1", base, 3000), _op("fusion.2", base + 3000,
                                                 2000),
                _op("fusion.3", base + 5000, 1000),
                _op("fusion.4", base + 6000, 4000)]
    ops += [_op("fusion.11", 40000, 120000), _op("fusion.12", 160000, 380000),
            _op("fusion.11", 600000, 40000)]
    trace = Trace([DeviceTrace("/device:TPU:0", ops, modules)], [], 0.0,
                  640000 * US)
    key = instruction_key(ops[0][0])
    names = {decode: {"fusion.1": "attn_index", "fusion.2": "mla",
                      "fusion.3": "kv_pool", "fusion.4": "moe_experts"},
             prefill: {"fusion.11": "attn_index", "fusion.12": "mla"}}
    if not scoped:
        names = {p: {n: "mlp" for n in m} for p, m in names.items()}
    maps = {p: {n: {key: s} for n, s in m.items()}
            for p, m in names.items()}

    def pair(start, end, whole, n_tail, prefix_len):
        return launches.Pair(
            {"kind": "prefill", "program": prefill, "n_tail": n_tail,
             "prefix_len": prefix_len},
            start * US, end * US, whole, False, not whole, None, 0.0, 0.0,
            None, None)

    joined = launches.Joined(
        [pair(40000, 540000, True, 6000, 2000),
         pair(600000, 640000, False, 9000, 0)], 2, "mark", None, None, 0.0,
        640000 * US)
    # two rows a wave, one of them still under index_topk
    rows = [{"prompt_len": 9000, "token_ts": [0.5, 1.0, 2.0]},
            {"prompt_len": 1500, "token_ts": [0.6, 1.0, 2.0]}]
    run = types.SimpleNamespace(
        trace=trace, rows=rows, t0=0.0, t1=3.0,
        ctx=types.SimpleNamespace(cell=cell, peaks=PEAKS),
        engine=types.SimpleNamespace(max_slots=32))
    run._program_reduce = {"launches": joined}
    return run, maps


def _counted(monkeypatch, selected, reachable):
    from ray_tpu.util.metrics import _registry

    def dump(value):
        return {"values": [((("program", "decode"),), value),
                           ((("program", "prefill"),), 7.0)]}

    monkeypatch.setattr(_registry, "snapshot", lambda: {
        "serve_index_selected_total": dump(selected),
        "serve_index_reachable_total": dump(reachable)}
        if reachable else {})


def test_the_four_readers_read_a_fixture(cell, monkeypatch):
    run, maps = _run(cell)
    monkeypatch.setattr(program, "_registry_maps", lambda: maps)
    _counted(monkeypatch, 260.0, 1000.0)
    got = {name: cells.load_reader(name)(run) for name in NEW}
    family, config = cell.family, cell.config
    # of 2 x 10,000 + 500,000 + 40,000 us of the two programs
    assert got[NEW[0]] == pytest.approx(
        100.0 * (6000 + 120000 + 40000) / 560000)
    # 6,000 us a step under the three scopes; a wave of two rows reaches
    # 9,001.5 + 1,501.5 positions and selects 2,048 + 1,501.5 of them
    least = max(
        family.sparse_decode_bytes(config, 2, 10503, 3549.5) / 819e9,
        family.sparse_decode_flops(config, 2, 10503, 3549.5) / 197e12)
    assert got[NEW[1]] == pytest.approx(100.0 * least / 6000e-6)
    # the whole prefill alone: 6,000 queries behind 2,000 positions
    pairs = 6000 * 2000 + 6000 * 6001 // 2
    assert got[NEW[2]] == pytest.approx(
        100.0 * family.index_prefill_flops(config, pairs) / 197e12 / 0.12)
    assert got[NEW[3]] == pytest.approx(26.0)
    assert 0 < got[NEW[1]] < 100 and 0 < got[NEW[2]] < 100


def test_a_program_without_the_scope_or_the_counters_reads_nothing(
        cell, monkeypatch):
    """The parent's programs have neither: the readers hand back None,
    and the line leaves the metrics out."""
    run, maps = _run(cell, scoped=False)
    monkeypatch.setattr(program, "_registry_maps", lambda: maps)
    _counted(monkeypatch, 0.0, 0.0)
    assert [cells.load_reader(name)(run) for name in NEW] == [None] * 4
    for name in NEW[:3]:
        assert cells.load_reader(name)(object()) is None
    other = cells.load_cell("kimi-k2-code.serve-offline-codegen")
    run, maps = _run(other)
    monkeypatch.setattr(program, "_registry_maps", lambda: maps)
    assert cells.load_reader(NEW[1])(run) is None
    assert cells.load_reader(NEW[2])(run) is None
