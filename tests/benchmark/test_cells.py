"""BENCHMARK.json against the files it names, the contract's limits,
and the shape of the result line."""

import json
import os
import re
import types

import pytest

from benchmark import cells, run
from benchmark.reduce import xplane as X

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def head_dim(cell) -> int:
    """The configuration's own head dimension: its ``head_dim`` where
    it has the key, else what its family's sizes give."""
    sizes = cell.family.sizes(cell.config)
    return cell.config.get("head_dim",
                           sizes["d_model"] // sizes["n_head"])


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_finds_its_files_by_name(name):
    cell = cells.load_cell(name, BENCH)
    assert name == f"{cell.config_name}.{cell.traffic_name}"
    assert cell.config["name"] == cell.config_name
    # what was cut from the source is said twice and agrees: the keys
    # in BENCHMARK.json (names, as the contract has them) and in the
    # file, which also keeps what the source had and the deployment
    # the cut stands for.  Nothing cut is as good: GPT-2's are whole.
    entry = next(c for c in BENCH["configs"]
                 if c["name"] == cell.config_name)
    reduced = cell.config["reduced"]
    assert reduced == entry["reduced"] and len(reduced) <= 16
    for key in reduced:
        assert NAME.match(key), key
        assert cell.config["reduced_from"][key] != cell.config[key]
    if reduced:
        assert cell.config["deployment"].strip()
    driver = cells.load_driver(cell.traffic["driver"])
    assert callable(driver.run)
    sizes = cell.family.sizes(cell.config)
    assert sizes["d_model"] > 0 and sizes["n_head"] > 0
    assert cell.family.attention_shape(cell.config)["head_dim"] \
        == head_dim(cell) > 0


#: what a family file must have; ``logit_tie_tol`` and
#: ``reference_kwargs`` it may have (``benchmark/families/gpt2.py``)
FAMILY_NAMES = ("REFERENCE", "sizes", "program", "param_count",
                "train_flops_per_token", "decode_step_bytes",
                "kv_bytes_per_token", "attention_shape",
                "aot_serve_programs")


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_finds_its_family_and_reference(config):
    """Drivers and readers ask the family file for whatever depends on
    the architecture; a configuration of a new family brings its own."""
    name = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == config)
    cell = cells.load_cell(name, BENCH)
    assert cell.family is cells.load_family(
        cell.config["program"]["family"])
    for attr in FAMILY_NAMES:
        assert hasattr(cell.family, attr), attr
    assert callable(cell.reference.logits) and callable(
        cell.reference.loss)
    shape = cell.family.attention_shape(cell.config)
    assert shape["head_dim"] == head_dim(cell) > 0
    assert 0 < shape.get("n_kv_head", shape["n_head"]) <= shape["n_head"]
    assert cell.family.param_count(cell.config) > 0
    assert cell.family.kv_bytes_per_token(cell.config) > 0
    assert isinstance(cell.reference_kwargs, dict)


def test_no_driver_or_reader_names_a_family():
    """The architecture is named in ``families/`` and ``reference/``
    alone (and in the tool that compiles a family's decode programs by
    asking the family for them)."""
    import glob

    shared = glob.glob(os.path.join(cells.HERE, "*.py")) \
        + glob.glob(os.path.join(cells.HERE, "drivers", "*.py")) \
        + glob.glob(os.path.join(cells.HERE, "metrics", "*.py"))
    assert len(shared) > 30
    for path in shared:
        with open(path) as f:
            text = f.read()
        assert "gpt2_" not in text and "reference.gpt2" not in text \
            and "reference import gpt2" not in text, path


@pytest.mark.parametrize("metric,file", [
    ("device_idle_share.chat", "device_idle_share"),
    ("compiles_in_window.train", "compiles_in_window"),
    ("decode_step_p50_ms.offline", "decode_step_p50_ms"),
    ("gap_tail_ms.chat", "gap_tail_ms.chat"),
    ("decode_hbm_roofline.offline", "decode_hbm_roofline.offline"),
    ("train_mfu", "train_mfu")])
def test_a_suffixed_metric_is_read_by_its_own_file_or_its_base(metric,
                                                               file):
    want = cells._load_module("metrics", file).read
    assert cells.load_reader(metric) is want


@pytest.mark.parametrize("name", CELLS)
def test_the_rehearsal_lays_tiny_files_over_the_cell(name):
    from benchmark import rehearse

    cell, tiny = cells.load_cell(name, BENCH), rehearse.tiny_cell(name)
    assert tiny.name == cell.name and tiny.chips == cell.chips
    family = cell.family
    assert family.sizes(tiny.config)["d_model"] \
        < family.sizes(cell.config)["d_model"]
    assert family.param_count(tiny.config) < 1_000_000
    assert tiny.config["program"]["family"] == \
        cell.config["program"]["family"]
    assert tiny.traffic["driver"] == cell.traffic["driver"]
    # what the tiny file does not name stays the cell's own
    for key in ("optimizer", "mesh", "batch_cycle"):
        assert tiny.traffic.get(key) == cell.traffic.get(key)
    # and the cell itself is untouched
    assert cells.load_cell(name, BENCH).traffic == cell.traffic


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_reports_what_the_contract_asks(name):
    cell = cells.load_cell(name, BENCH)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e, (m["name"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader_of_its_own(metric):
    read = cells.load_reader(metric)
    empty = types.SimpleNamespace(setup_s=1.0, compiles_in_window=0)
    # nothing to read: no value, and no crash
    assert read(empty) in (None, 0.0, 1.0)


def test_contract_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", []):
            assert w in CELLS
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(cells.ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    size = os.path.getsize(os.path.join(cells.ROOT, "BENCHMARK.json"))
    assert size <= 64 * 1024


def test_unknown_cell_is_refused():
    with pytest.raises(SystemExit):
        cells.load_cell("gpt2-xl.no-such-traffic", BENCH)


def _fake_trace():
    dev = X.DeviceTrace(
        "/device:TPU:0", [("%fusion.1 = f32[] fusion()", 0.0, 6e8)],
        [("jit_step(1)", 0.0, 6e8)])
    return X.windowed([dev], [("bench.fence", 6e8, 4e8)])


@pytest.mark.parametrize("traced", [False, True])
def test_result_line_keys(traced):
    cell = cells.load_cell("gpt2-124m.train-1chip", BENCH)
    fences = [10.0 + 0.25 * i for i in range(41)]
    ctx = types.SimpleNamespace(
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        cell=cell)
    fake = types.SimpleNamespace(
        ctx=ctx, setup_s=18.0, correct=True, attempted=40, failed=0,
        fences=fences, tokens_per_step=24 * 1024, chips=1,
        compiles_in_window=0, flops_per_token=8.03e8,
        memory_peak_bytes=13762435072,
        shapes={"batch": 24, "seq": 1024, "n_head": 12, "head_dim": 64,
                "n_layer": 12, "d_model": 768},
        trace=_fake_trace() if traced else None)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    line = json.loads(json.dumps(run.result_line(fake, cell, traced,
                                                 device)))
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["device"]["memory_peak_bytes"] == 13762435072
    want = cell.per_layer if traced else cell.end_to_end
    assert set(line["metrics"]) <= {m["name"] for m in want}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if traced:
        assert line["device"]["busy_s"] == pytest.approx(0.6)
        assert line["device"]["window_s"] == pytest.approx(0.6)
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert line["metrics"]["train_step_p50_ms"]["value"] == \
            pytest.approx(250.0)
        assert line["metrics"]["train_mfu"]["value"] == pytest.approx(
            100 * 8.03e8 * 24 * 1024 / 0.25 / 197e12)
    else:
        assert line["metrics"]["train_tokens_per_s_chip"]["value"] == \
            pytest.approx(24 * 1024 / 0.25)
        assert line["metrics"]["setup_s"]["value"] == 18.0
        assert "breakdown" not in line
