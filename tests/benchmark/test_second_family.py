"""A second model family through the whole harness, as a later PR would
bring it: files and entries (``tests/benchmark/data/second_family``),
no edit to a file that is there.

The family is the program's ``llama`` at toy size, written with a
published ``config.json``'s keys, head dimension 128, cut in depth.  It
is loaded through ``benchmark.cells`` from its own tree, walked through
``benchmark.run.run_cell`` on the CPU the way ``benchmark/rehearse.py``
walks a cell, and laid over a copy of the checkout in which the
harness's own tests then run.
"""

import copy
import importlib
import json
import os
import shutil
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, rehearse, serving
from benchmark.reduce import program as P

TREE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "second_family")
ADD = cells.load_json(TREE, "BENCHMARK.add.json")
SERVE, TRAIN = (w["name"] for w in ADD["workloads"])
#: the keys a published llama-lineage config.json spells its sizes with
PUBLISHED_KEYS = ("hidden_size", "num_attention_heads",
                  "num_key_value_heads", "num_hidden_layers",
                  "intermediate_size")


def merged_bench(extra_metrics: bool = False):
    """BENCHMARK.json as it would read after the PR that adds the
    family: its configurations and cells appended, each new cell in the
    ``workloads`` of every metric the cell it is like reports."""
    bench = copy.deepcopy(cells.load_benchmark())
    bench["configs"] += ADD["configs"]
    bench["workloads"] += ADD["workloads"]
    for new, like in ADD["reports_like"].items():
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(new)
    if extra_metrics:
        bench["per_layer"] += ADD["per_layer"]
    return bench


def fixture_cell(name):
    return cells.load_cell(name, merged_bench(), root=TREE)


def test_its_files_are_found_under_the_tree_it_was_loaded_from():
    cell = fixture_cell(SERVE)
    assert cell.root == TREE
    for key in PUBLISHED_KEYS:
        assert key in cell.config, key
    assert "n_embd" not in cell.config
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert cell.traffic["first_send_spread_s"] == 8.0
    family = cell.family
    assert family.__file__.startswith(TREE)
    assert cell.reference.__file__.startswith(TREE)
    assert family.attention_shape(cell.config)["head_dim"] == 128
    assert cell.reference_kwargs == {"rope_theta": 500000.0,
                                     "rms_eps": 1e-06}
    # the checkout's own tree has no such family, and is not searched
    with pytest.raises(SystemExit):
        cells.load_family("llama")
    with pytest.raises(FileNotFoundError):
        cells.load_cell(SERVE, merged_bench())
    tiny = rehearse.tiny_cell(TRAIN, merged_bench(), TREE)
    assert tiny.config["name"] == "rehearsal-llama"
    assert tiny.family is cells.load_family("llama", TREE)


def test_the_command_resolves_what_it_always_did():
    """No root given: the checkout's own files, family and reference."""
    cell = cells.load_cell("gpt2-xl.serve-offline-decode")
    assert cell.root == cells.ROOT
    assert cell.family.__file__ == os.path.join(cells.HERE, "families",
                                                "gpt2.py")
    assert cell.reference.__file__ == os.path.join(
        cells.HERE, "reference", "gpt2.py")
    assert cell.reference_kwargs == {}


def test_the_near_tie_tolerance_is_the_familys_where_it_states_one():
    """``families/gpt2.py`` states none: GPT-2's cells keep 0.03 for
    twelve layers and 0.06 for the XL's 48."""
    small = cells.load_cell("gpt2-124m.train-1chip")
    xl = cells.load_cell("gpt2-xl.serve-offline-decode")
    assert not hasattr(xl.family, "logit_tie_tol")
    assert serving.tie_tol(small, 12) == pytest.approx(0.03)
    assert serving.tie_tol(xl, 48) == pytest.approx(0.06)
    assert serving.tie_tol(fixture_cell(SERVE), 48) == 1e-3


def test_param_count_is_the_programs():
    from ray_tpu.models import llama_param_count

    cell = fixture_cell(TRAIN)
    model = cell.family.program(cell.config, {})
    assert cell.family.param_count(cell.config) == \
        llama_param_count(model.cfg) == 1_246_464
    assert model.cfg.rope_theta == 500000.0 and model.cfg.rms_eps == 1e-06
    assert cell.family.kv_bytes_per_token(cell.config) == 2 * 2 * 128 * 2
    assert cell.family.decode_step_bytes(cell.config, 10) \
        - cell.family.decode_step_bytes(cell.config, 0) == 10 * 1024


@pytest.fixture(scope="module")
def toy():
    """The fixture's configuration in float32, norms off their initial
    ones so that a swapped or dropped one shows."""
    cell = fixture_cell(TRAIN)
    model = cell.family.program(cell.config, {
        "dtype": jnp.float32, "use_flash": False, "remat": False})
    params = model.init(jax.random.PRNGKey(3))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(
            jax.random.PRNGKey(x.size % 977), x.shape, x.dtype), params)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (3, 33), 0, model.cfg.vocab_size))
    return cell, model, params, tokens


def test_the_reference_matches_the_program(toy):
    from ray_tpu.models import llama_forward

    cell, model, params, tokens = toy
    vocab = model.cfg.vocab_size
    with jax.default_matmul_precision("highest"):
        want = llama_forward(params, tokens[:, :-1], model.cfg)
        want_loss = float(model.loss(params, {"tokens": tokens}))
    got = cell.reference.logits(params, tokens[:, :-1], vocab_size=vocab,
                                **cell.reference_kwargs)
    assert got.shape == (3, 32, vocab)
    np.testing.assert_allclose(got, want[..., :vocab], atol=2e-4,
                               rtol=2e-4)
    assert float(cell.reference.loss(
        params, tokens, vocab_size=vocab,
        **cell.reference_kwargs)) == pytest.approx(want_loss, rel=1e-5)
    # what the family reads from the configuration reaches the
    # reference: the program's own defaults are another model
    other = cell.reference.logits(params, tokens[:, :-1], vocab_size=vocab,
                                  rope_theta=10000.0, rms_eps=1e-5)
    assert float(np.abs(np.asarray(other) - np.asarray(got)).max()) > 1e-2
    # right-padding cannot reach an earlier position (the serving check
    # pads to max_seq)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :10] = tokens[0, :10]
    long = cell.reference.logits(params, padded, vocab_size=vocab,
                                 **cell.reference_kwargs)
    np.testing.assert_allclose(long[:, :10], got[:1, :10], atol=1e-5)


def test_its_serving_programs_trace_over_abstract_arguments(toy):
    """``aot_serve_programs`` as ``benchmark/aot_fit.py`` uses it, short
    of the chip's compiler: both programs evaluate over shapes, and the
    pool they carry is the one ``kv_bytes_per_token`` counts."""
    cell, model, _, _ = toy
    cfg, slots, block, blocks = model.cfg, 4, 16, 24
    cache_shapes, programs = cell.family.aot_serve_programs(
        cfg, slots, block, 32, jax.ShapeDtypeStruct)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = cache_shapes(blocks)
    assert cache["k"].shape == (2, blocks, block, 1, 128)
    per_token = 2 * cache["k"].size // (blocks * block) * 2   # k and v, bf16
    assert per_token == cell.family.kv_bytes_per_token(cell.config)
    assert [name for name, _, _ in programs] == ["decode", "prefill"]
    for _, fn, rest in programs:
        toks, after = jax.eval_shape(fn, params, cache, *rest)
        assert toks.dtype == jnp.int32
        assert after["k"].shape == cache["k"].shape


def test_the_pool_has_the_kv_heads_shape():
    """``kv_pool_time_share`` claims pool-shaped copies by shape: under
    grouped-query attention the pool holds the K/V heads, not the query
    heads (``families/gpt2.py`` states no ``n_kv_head``: its 25)."""
    def run_of(cell):
        return types.SimpleNamespace(
            ctx=types.SimpleNamespace(cell=cell),
            engine=types.SimpleNamespace(n_blocks=96, block=16))

    assert P._pool_dims(run_of(fixture_cell(SERVE))) == (2, 96, 16, 1, 128)
    xl = cells.load_cell("gpt2-xl.serve-offline-decode")
    assert P._pool_dims(run_of(xl)) == (48, 96, 16, 25, 64)
    assert P._pool_dims(types.SimpleNamespace(setup_s=1.0)) is None
    pool = "bf16[2,96,16,1,128]{4,3,2,1,0}"
    assert P.is_pool_copy(f"%copy.3 = {pool} copy(%x)", (2, 96, 16, 1, 128))
    assert not P.is_pool_copy(f"%copy.3 = {pool} copy(%x)",
                              (2, 96, 16, 2, 128))


@pytest.fixture
def on_the_cpu(monkeypatch, tmp_path):
    """What ``benchmark/rehearse.py main`` arranges, undone afterwards:
    the kernels in interpret mode, the compile cache and the trace in
    the test's own directory, JAX's settings as they were."""
    from ray_tpu._private import compile_cache

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "flash_attention", flash.flash_attention)
    rehearse.interpret_kernels()
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    monkeypatch.chdir(tmp_path)
    was = {name: getattr(jax.config, name)
           for name in compile_cache._SETTINGS}
    yield
    for name, value in was.items():
        jax.config.update(name, value)


@pytest.mark.parametrize("name", [SERVE, TRAIN])
def test_a_second_family_walks_the_whole_command(name, on_the_cpu, capsys):
    """Both drivers, untraced and traced, through ``run.run_cell``:
    prefill then decode through the paged cache, and the train step's
    loss, against the plain float32 reference; every per-layer reader
    gives a number or nothing."""
    bench = merged_bench()
    cell = rehearse.tiny_cell(name, bench, TREE)
    assert cell.config["hidden_size"] // \
        cell.config["num_attention_heads"] != 64
    for traced in (0, 1):
        line = json.loads(json.dumps(rehearse.walk(cell, traced)))
        said = capsys.readouterr().out
        assert line["correct"] is True, said
        assert {"correct", "attempted", "failed", "metrics",
                "device"} <= set(line)
        assert line["failed"] == 0 and line["attempted"] > 0
        assert line["device"]["platform"] == "cpu"
        want = cell.per_layer if traced else cell.end_to_end
        assert set(line["metrics"]) <= {m["name"] for m in want}
        for m in line["metrics"].values():
            assert set(m) == {"value", "unit"}
            assert np.isfinite(m["value"])
        if not traced:
            # every end-to-end metric of the cell it is like
            assert set(line["metrics"]) == {m["name"] for m in want}
        if name == SERVE:
            assert "[correct] ok=true" in said
            assert '"repeat_hit"' in said and "hit_blocks=" in said
            assert "tol=0.001" in said          # the family's own
            assert "ramp_s=" in said            # its clients staggered
        else:
            assert "rtol=0.0002" in said


def _lay_over(src, dst):
    for folder, _, files in os.walk(src):
        for f in files:
            target = os.path.join(dst, os.path.relpath(folder, src), f)
            assert not os.path.exists(target), f"{target} is there already"
            os.makedirs(os.path.dirname(target), exist_ok=True)
            shutil.copy(os.path.join(folder, f), target)


def test_the_harness_tests_pass_with_the_family_added(tmp_path):
    """A copy of the checkout's benchmark, this family's files laid
    over it (none may be there already), its entries and one reader of
    a scope more added to BENCHMARK.json: the harness's own tests, which
    a model's PR may not edit, pass there as they are."""
    ignore = shutil.ignore_patterns("__pycache__", "second_family",
                                    "*.pb")
    shutil.copytree(cells.HERE, tmp_path / "benchmark", ignore=ignore)
    shutil.copytree(os.path.dirname(os.path.abspath(__file__)),
                    tmp_path / "tests" / "benchmark", ignore=ignore)
    (tmp_path / "tests" / "__init__.py").write_text("")
    _lay_over(os.path.join(TREE, "benchmark"), tmp_path / "benchmark")
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(merged_bench(extra_metrics=True), f, indent=1)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [str(tmp_path), cells.ROOT]))
    files = ["test_cells.py", "test_traffic.py", "test_program_reduce.py"]
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist",
         *[os.path.join("tests", "benchmark", f) for f in files]],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=600)
    tail = proc.stdout[-4000:] + proc.stderr[-2000:]
    assert proc.returncode == 0, tail
    # it ran the copy's tests on the copy's BENCHMARK.json: the new
    # cells, configuration and reader are among the cases that passed
    passed = [ln for ln in proc.stdout.splitlines() if "PASSED" in ln]
    for case in (f"finds_its_files_by_name[{SERVE}]",
                 "finds_its_family_and_reference[llama-fixture]",
                 f"lays_tiny_files_over_the_cell[{TRAIN}]",
                 "has_a_reader_of_its_own[sample_time_share.offline]",
                 "quote_registered_scopes"):
        assert any(case in ln for ln in passed), (case, tail)
