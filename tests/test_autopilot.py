"""Autopilot acceptance suite: the closed tuning loop end to end.

Four layers:

1. **attribution goldens** — a canned v5e registry snapshot with one
   compute-bound, one HBM-bound and one unmeasured program must
   classify, rank and name the bottleneck exactly.
2. **planner** — the variant-hash mirror stays in lockstep with what
   ``sweep_tpu.run_sweep`` records (train, decode and traffic modes,
   with stubbed harnesses), and the ledger grading (unmeasured /
   stale / regressed / fresh) drives priority and the ``--budget`` cap.
3. **verdict** — a synthetic regressed history exits non-zero naming
   the regressed metric and files AUTOPILOT.md/.json.
4. **satellites** — ledger provenance stamping, ``perfledger publish``
   (CPU refusal / --allow-cpu / --dry-run), the deduped peak-FLOPs
   table, and the engine_stats ``device`` roofline block.
"""

import argparse
import io
import json
import os
import pathlib
import subprocess
import sys

import pytest

from ray_tpu.tools import perfledger as pl
from ray_tpu.tools.autopilot import attribution, planner
from ray_tpu.tools.autopilot import verdict as verdict_mod
from ray_tpu.tools.autopilot.__main__ import main as ap_main

pytestmark = pytest.mark.fast

ROOT = pathlib.Path(__file__).resolve().parents[1]

#: a v5e roofline block (engine_stats "device" shape): ridge ≈ 240
_V5E = {"backend": "tpu", "device_kind": "TPU v5e",
        "peak_flops_per_chip": 197e12,
        "peak_hbm_bytes_per_sec": 819e9,
        "ridge_flops_per_byte": 240.5}

#: canned registry snapshot: train.step is compute-bound (AI 400 above
#: the ridge) at 1/3 of walltime; serve.decode is HBM-bound (AI 50)
#: at 2/3 of walltime with bytes sized for exactly 50% bandwidth
#: utilization; serve.prefill compiled but never invoked (unmeasured).
_SNAPSHOT = {
    "train.step": {
        "compile_events": 1, "invokes": 100,
        "invoke_ms": {"count": 100, "mean": 10.0, "p50": 10.0,
                      "p95": 11.0, "p99": 12.0, "max": 13.0},
        "arithmetic_intensity": 400.0, "mfu": 0.45,
        "bytes_accessed": 4e9, "recompile_storm": False},
    "serve.decode": {
        "compile_events": 1, "invokes": 400,
        "invoke_ms": {"count": 400, "mean": 5.0, "p50": 5.0,
                      "p95": 6.0, "p99": 7.0, "max": 8.0},
        "arithmetic_intensity": 50.0, "mfu": 0.05,
        # 0.005 s * 819e9 B/s * 0.5 -> half the bandwidth ceiling
        "bytes_accessed": 0.005 * 819e9 * 0.5,
        "recompile_storm": False},
    "serve.prefill": {
        "compile_events": 2, "invokes": 0,
        "invoke_ms": {"count": 0, "mean": None, "p50": None,
                      "p95": None, "p99": None, "max": None},
        "arithmetic_intensity": None, "mfu": None,
        "bytes_accessed": None, "recompile_storm": False},
}


def _bench_rec(value, metric="ap_tokens_per_sec"):
    return {"metric": metric, "value": value, "unit": "tok/s",
            "vs_baseline": None, "detail": {}}


def _write_entries(path, entries):
    with open(path, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")


def _entry(rec, prov=None):
    return {"recorded_at": "2026-08-05 00:00:00", "source": "sweep",
            "provenance": prov or {}, "record": rec,
            "metrics": pl.extract_metrics(rec)}


def _cand(cid):
    return next(c for c in planner.CANDIDATES if c["id"] == cid)


# ---------------------------------------------------------------------------
# 1. attribution goldens
# ---------------------------------------------------------------------------

def test_classify_against_ridge():
    assert attribution.classify(400.0, 240.5) == "compute-bound"
    assert attribution.classify(50.0, 240.5) == "hbm-bound"
    assert attribution.classify(240.5, 240.5) == "compute-bound"
    assert attribution.classify(None, 240.5) == "unmeasured"


def test_attribution_golden_classes_and_bottleneck():
    rep = attribution.attribute(_SNAPSHOT, device=_V5E)
    progs = rep["programs"]
    assert progs["train.step"]["class"] == "compute-bound"
    assert progs["serve.decode"]["class"] == "hbm-bound"
    assert progs["serve.prefill"]["class"] == "unmeasured"
    # time shares: 1000ms train vs 2000ms decode vs 0
    assert progs["train.step"]["time_share"] == pytest.approx(
        1 / 3, abs=1e-4)
    assert progs["serve.decode"]["time_share"] == pytest.approx(
        2 / 3, abs=1e-4)
    assert progs["serve.prefill"]["time_share"] == 0.0
    # headroom: compute-bound is 1-mfu; hbm-bound is 1-bw_util
    assert progs["train.step"]["headroom"] == pytest.approx(0.55)
    assert progs["serve.decode"]["headroom"] == pytest.approx(0.5)
    assert progs["serve.prefill"]["headroom"] is None
    # decode's headroom-weighted share (2/3 * 0.5) beats train's
    # (1/3 * 0.55) -> decode is THE bottleneck
    assert rep["ranked"][0] == "serve.decode"
    assert rep["bottleneck"] == "serve.decode"
    assert "serve.decode" in rep["summary"]
    assert "hbm-bound" in rep["summary"]
    # the knobs come from the attribution catalog
    assert "kv_layout" in progs["serve.decode"]["knobs"]


def test_attribution_no_invokes_has_no_bottleneck():
    rep = attribution.attribute(
        {"serve.prefill": _SNAPSHOT["serve.prefill"]}, device=_V5E)
    assert rep["bottleneck"] is None
    rep = attribution.attribute({}, device=_V5E)
    assert rep["bottleneck"] is None
    assert rep["summary"] == "no programs registered"


def test_attribute_registry_uses_local_roofline():
    # tests run on the forced-CPU backend: peak 1e12 / 1e11 -> ridge 10
    rep = attribution.attribute_registry()
    assert rep["device"]["ridge_flops_per_byte"] == pytest.approx(10.0)


def test_program_knobs_cover_known_programs():
    from ray_tpu._private.device_stats import KNOWN_PROGRAMS

    assert set(attribution.PROGRAM_KNOBS) == set(KNOWN_PROGRAMS)


# ---------------------------------------------------------------------------
# 2. planner: mirror lockstep + ledger grading
# ---------------------------------------------------------------------------

def _stub_time_config(*a, **k):
    return (50000.0, 0.4, 2.5, 1,
            {"mfu_xla": 0.42, "xla_flops": 1e12, "peak_hbm_bytes": 2e9})


def _stub_time_decode(*a, **k):
    stats = {"ttft_ms": {"p50": 1.0, "p95": 2.0},
             "inter_token_ms": {"p50": 0.5, "p95": 0.9},
             "tokens_per_sec": 1000.0}
    return 3.0, 1000.0, stats, 1


def test_mirror_matches_sweep_record_train_and_decode(monkeypatch,
                                                     tmp_path):
    import sweep_tpu

    monkeypatch.setattr(sweep_tpu, "time_config", _stub_time_config)
    monkeypatch.setattr(sweep_tpu, "time_decode", _stub_time_decode)
    monkeypatch.setattr(sweep_tpu, "decode_mesh",
                        lambda tensor: (None, tensor))
    hist = str(tmp_path / "hist.jsonl")
    grid = [[32, {"ce_impl": "pallas"}], [8, {"mode": "decode"}]]
    recs = sweep_tpu.run_sweep(grid, n_chips=1, out=io.StringIO(),
                               ledger=True, ledger_path=hist)
    assert all("failed" not in r for r in recs)
    for (batch, overrides), rec in zip(grid, recs):
        assert rec["sweep"] == planner.mirror_variant(batch, overrides)
    # the mirrored hash finds the recorded series
    series = pl.metric_series(pl.load_history(hist))
    for batch, overrides in grid:
        suffix = "#" + pl._variant_key(
            planner.mirror_variant(batch, overrides))
        assert any(n.endswith(suffix) for n in series), overrides


def test_mirror_matches_sweep_record_traffic(monkeypatch, tmp_path):
    """The traffic variant now carries block_size/prefill_bucket in its
    identity (they used to be popped into run_kw first, hashing a
    16-vs-64 block A/B into ONE series) — and the planner mirror must
    reproduce that identity exactly."""
    import sweep_tpu
    from ray_tpu.serve import traffic as traffic_mod

    fake_rep = {
        "offered": 4, "completed": 4, "shed": 0,
        "prefix_hit_rate": 0.5, "slo_attainment": 1.0, "slo": None,
        "spec_accept_rate": None,
        "latency_ms": {"p50": 10.0, "p95": 20.0},
        "engine": {"tokens_per_sec": 100.0, "mesh": None,
                   "ttft_ms": {"p50": 1.0, "p95": 2.0},
                   "kv_cache": None, "rejections_by_reason": {}}}
    monkeypatch.setattr(traffic_mod, "run_traffic",
                        lambda *a, **k: fake_rep)
    monkeypatch.setattr(sweep_tpu, "decode_mesh",
                        lambda tensor: (None, tensor))
    overrides = {"mode": "traffic", "kv_layout": "paged",
                 "block_size": 32}
    recs = sweep_tpu.run_sweep([[8, dict(overrides)]], n_chips=1,
                               out=io.StringIO(), ledger=False)
    assert recs[0]["sweep"] == planner.mirror_variant(8, overrides)
    assert recs[0]["sweep"]["block_size"] == 32
    # a block-size A/B forms two distinct series
    a = planner.mirror_variant(8, overrides)
    b = planner.mirror_variant(8, dict(overrides, block_size=64))
    assert pl._variant_key(a) != pl._variant_key(b)


def test_plan_unmeasured_budget_and_schema(tmp_path):
    hist = str(tmp_path / "empty.jsonl")
    p = planner.plan(history=hist, budget=3)
    assert len(p["grid"]) == 3
    assert all(v["status"] == "unmeasured" for v in p["variants"])
    for batch, overrides in p["grid"]:
        assert isinstance(batch, int) and isinstance(overrides, dict)
    # rationale strings ride in the plan report, not in the overrides
    # (sweep_tpu passes unknown overrides into the model config)
    assert all("rationale" not in ov for _, ov in p["grid"])
    assert all(v["rationale"] for v in p["variants"])


def test_plan_stale_and_fresh_detection(tmp_path):
    cand = _cand("decode-b8")
    variant = planner.mirror_variant(cand["batch"], cand["overrides"])
    rec = {"sweep": variant, "decode_tok_s": 1000.0}
    hist = str(tmp_path / "hist.jsonl")
    current = pl.provenance().get("git_sha")
    assert current, "tests run inside the repo checkout"
    # measured at a different SHA -> stale
    _write_entries(hist, [_entry(rec, prov={"git_sha": "deadbee"})])
    p = planner.plan(history=hist, budget=99)
    byid = {v["id"]: v for v in p["variants"]}
    assert byid["decode-b8"]["status"] == "stale"
    assert "deadbee" in byid["decode-b8"]["rationale"]
    # measured at the current SHA -> fresh, dropped from the plan
    _write_entries(hist, [_entry(rec, prov={"git_sha": current})])
    p = planner.plan(history=hist, budget=99)
    assert "decode-b8" in p["skipped_fresh"]
    assert "decode-b8" not in {v["id"] for v in p["variants"]}
    # ...unless explicitly included
    p = planner.plan(history=hist, budget=99, include_fresh=True)
    byid = {v["id"]: v for v in p["variants"]}
    assert byid["decode-b8"]["status"] == "fresh"


def test_plan_regressed_candidate_ranks_first(tmp_path):
    cand = _cand("traffic-paged")
    variant = planner.mirror_variant(cand["batch"], cand["overrides"])
    hist = str(tmp_path / "hist.jsonl")
    _write_entries(hist, [
        _entry({"sweep": variant, "slo_attainment": 0.99}),
        _entry({"sweep": variant, "slo_attainment": 0.50}),
    ])
    p = planner.plan(history=hist, budget=4)
    assert p["variants"][0]["id"] == "traffic-paged"
    assert p["variants"][0]["status"] == "regressed"
    assert "REGRESSED" in p["variants"][0]["rationale"]


def test_plan_biases_toward_attributed_bottleneck(tmp_path):
    hist = str(tmp_path / "empty.jsonl")
    att = attribution.attribute(_SNAPSHOT, device=_V5E)
    p = planner.plan(history=hist, budget=4, attribution=att)
    assert p["bottleneck"] == "serve.decode"
    # every candidate is unmeasured, so the serve.decode-targeting
    # ones (bonus 0.5) must lead the grid, in catalog order
    assert [v["id"] for v in p["variants"]][:3] == [
        "decode-b8", "decode-b16", "decode-b16-flash"]
    assert "targets bottleneck serve.decode" \
        in p["variants"][0]["rationale"]


@pytest.mark.parametrize("seeded", [True, False],
                         ids=["bench-only-history", "absent-history"])
def test_plan_on_thin_history_is_nonempty_and_runnable(
        monkeypatch, tmp_path, seeded):
    """Acceptance: `autopilot plan` over a history that holds bench
    lines but no sweep variant yet — or over no history file at all,
    which reads as an empty one — emits a non-empty grid sweep_tpu
    accepts (stubbed harness), and the measurement lands under the
    planner's predicted hash — after which the candidate grades
    fresh."""
    import sweep_tpu

    first = str(tmp_path / "first.jsonl")
    if seeded:
        pl.append_records(
            [{"metric": "gpt2_124m_train_tokens_per_sec_per_chip",
              "value": 91965.0, "unit": "tokens/s/chip",
              "vs_baseline": 0.872,
              "detail": {"backend": "tpu", "batch": 24, "chips": 1,
                         "seq": 1024, "remat_policy": "mlp_only"}},
             _bench_rec(10.1, "gpt2_decode_spec_cpu_smoke_tokens_per_sec")],
            "bench", path=first)
    p = planner.plan(history=first, budget=4)
    assert p["grid"]
    train_entries = [g for g in p["grid"] if "mode" not in g[1]]
    assert train_entries, "a thin history leaves train A/Bs queued"
    monkeypatch.setattr(sweep_tpu, "time_config", _stub_time_config)
    hist = str(tmp_path / "hist.jsonl")
    recs = sweep_tpu.run_sweep(train_entries[:1], n_chips=1,
                               out=io.StringIO(), ledger=True,
                               ledger_path=hist)
    assert "failed" not in recs[0]
    ran_id = next(v["id"] for v in p["variants"]
                  if [v["batch"], v["overrides"]] == train_entries[0])
    p2 = planner.plan(history=hist, budget=99)
    assert ran_id in p2["skipped_fresh"]


def test_candidate_overrides_survive_config_validation():
    """Every catalog candidate's leftover overrides must build a real
    GPT2Config — an invalid enum value (e.g. ce_impl="fused" for what
    this repo calls "streaming_xla") would make the planner emit a grid
    sweep_tpu accepts structurally but fails at config time, wasting
    the whole TPU session the plan was supposed to spend."""
    from ray_tpu.models import gpt2_config

    for cand in planner.CANDIDATES:
        mirror = planner.mirror_variant(cand["batch"],
                                        dict(cand["overrides"]))
        mode = mirror.get("mode", "train")
        if mode in ("traffic", "traffic_fleet"):
            assert mirror["kv_layout"] in ("dense", "paged"), cand["id"]
        gpt2_config("nano", **mirror["overrides"])


def test_sweep_autopilot_flag_appends_attribution(monkeypatch,
                                                  tmp_path):
    import sweep_tpu

    monkeypatch.setattr(sweep_tpu, "time_config", _stub_time_config)
    recs = sweep_tpu.run_sweep([[32, {}]], n_chips=1,
                               out=io.StringIO(), ledger=False,
                               autopilot=True)
    assert "autopilot" in recs[-1]
    assert "summary" in recs[-1]["autopilot"]


def test_attribution_over_real_bench_names_bottleneck(monkeypatch):
    """End-to-end, no stubs: a real (tiny) time_config run must leave a
    steady-state invoke window in the registry — bench.py books
    dt/n_steps per step after the fence — so attribute_registry() can
    name bench.train_step.  Regression for the compile-only gap where
    bench.train_step recorded 0 invokes and train sweeps had nothing
    to attribute."""
    import bench
    from ray_tpu._private import device_stats as ds

    ds.reset_registry()
    bench.time_config(2, seq=64, preset="nano", n_steps=2)
    rep = attribution.attribute_registry()
    prog = rep["programs"]["bench.train_step"]
    assert prog["invokes"] == 2
    assert prog["time_share"] == 1.0
    assert rep["bottleneck"] == "bench.train_step"


def test_bench_autopilot_flag_emits_attribution(monkeypatch, capsys):
    import bench

    monkeypatch.setattr(bench, "_EMITTED", [])
    bench._maybe_autopilot(argparse.Namespace(autopilot=True))
    out = capsys.readouterr().out
    rec = json.loads(out)
    assert "summary" in rec["autopilot"]
    assert bench._EMITTED and "autopilot" in bench._EMITTED[0]


# ---------------------------------------------------------------------------
# 3. verdict
# ---------------------------------------------------------------------------

def test_verdict_regressed_history_exits_nonzero(tmp_path, capsys):
    """Acceptance: `autopilot verdict` on a synthetic regressed history
    exits non-zero NAMING the regressed metric, and files both
    reports."""
    hist = str(tmp_path / "hist.jsonl")
    pl.append_records([_bench_rec(100.0)], "bench", path=hist)
    pl.append_records([_bench_rec(50.0)], "bench", path=hist)
    rc = ap_main(["--history", hist, "verdict",
                  "--out-dir", str(tmp_path)])
    captured = capsys.readouterr()
    assert rc == 1
    assert "ap_tokens_per_sec" in captured.err
    md = (tmp_path / "AUTOPILOT.md").read_text()
    assert "REGRESSED" in md and "ap_tokens_per_sec" in md
    assert "Next plan" in md
    v = json.loads((tmp_path / "AUTOPILOT.json").read_text())
    assert v["regressed"] == ["ap_tokens_per_sec"]
    assert v["ok"] is False
    assert v["plan"]["grid"], "verdict embeds the refreshed plan"


def test_verdict_clean_history_exits_zero(tmp_path, capsys):
    hist = str(tmp_path / "hist.jsonl")
    pl.append_records([_bench_rec(100.0)], "bench", path=hist)
    pl.append_records([_bench_rec(101.0)], "bench", path=hist)
    rc = ap_main(["--history", hist, "verdict", "--no-write"])
    assert rc == 0
    assert "**OK**" in capsys.readouterr().out


def test_verdict_flags_baseline_regression_and_unmeasured(tmp_path):
    hist = str(tmp_path / "hist.jsonl")
    pl.append_records([_bench_rec(50.0)], "bench", path=hist)
    base = tmp_path / "BASELINE.json"
    base.write_text(json.dumps({"published": {
        "ap_tokens_per_sec": 100.0, "never_measured_metric": 1.0}}))
    v = verdict_mod.build_verdict(history=hist, baseline=str(base))
    # single point -> "new" vs previous, but regressed vs baseline
    assert v["baseline_regressed"] == ["ap_tokens_per_sec"]
    assert "ap_tokens_per_sec" in v["regressed"]
    assert v["unmeasured_baseline"] == ["never_measured_metric"]
    assert v["ok"] is False


# ---------------------------------------------------------------------------
# 4. satellites: provenance, publish, peak table, engine_stats device
# ---------------------------------------------------------------------------

def test_ledger_entries_carry_provenance(tmp_path):
    hist = str(tmp_path / "hist.jsonl")
    pl.append_records([_bench_rec(10.0)], "bench", path=hist)
    entry = pl.load_history(hist)[0]
    prov = entry["provenance"]
    assert set(prov) == {"git_sha", "jax_version", "backend",
                         "device_kind", "hostname"}
    assert prov["git_sha"], "stamped from the repo checkout"
    # conftest imported jax on the forced-CPU backend
    assert prov["backend"] == "cpu"
    assert pl.entry_backend(entry) == "cpu"


def test_publish_refuses_cpu_then_allows(tmp_path, capsys):
    hist = str(tmp_path / "hist.jsonl")
    base = str(tmp_path / "BASELINE.json")
    pl.append_records([_bench_rec(100.0)], "bench", path=hist)
    with pytest.raises(ValueError, match="CPU backend"):
        pl.publish("latest", history=hist, baseline=base)
    assert pl.main(["--history", hist, "publish", "latest",
                    "--baseline", base]) == 2
    assert "publish refused" in capsys.readouterr().err
    assert not os.path.exists(base)
    # dry-run computes the diff without writing
    res = pl.publish("latest", history=hist, baseline=base,
                     allow_cpu=True, dry_run=True)
    assert res["written"] is False
    assert res["diff"]["ap_tokens_per_sec"]["new"] == 100.0
    assert not os.path.exists(base)
    # the real publish arms the baseline gate
    assert pl.main(["--history", hist, "publish", "latest",
                    "--baseline", base, "--allow-cpu"]) == 0
    capsys.readouterr()
    assert pl.load_baseline(base) == {"ap_tokens_per_sec": 100.0}
    # ...and check() now grades against it
    pl.append_records([_bench_rec(50.0)], "bench", path=hist)
    result = pl.check(hist, base)
    assert result["verdicts"]["ap_tokens_per_sec"][
        "baseline_verdict"] == "regress"
    assert result["ok"] is False


def test_publish_by_index_and_bad_selectors(tmp_path):
    hist = str(tmp_path / "hist.jsonl")
    base = str(tmp_path / "BASELINE.json")
    pl.append_records([_bench_rec(100.0), _bench_rec(120.0)], "bench",
                      path=hist)
    res = pl.publish("0", history=hist, baseline=base, allow_cpu=True)
    assert res["published"]["ap_tokens_per_sec"] == 100.0
    with pytest.raises(ValueError, match="out of range"):
        pl.publish("9", history=hist, baseline=base, allow_cpu=True)
    # publishing preserves unrelated BASELINE.json keys
    data = json.loads(pathlib.Path(base).read_text())
    assert set(data) == {"published"}


def test_publish_preserves_other_baseline_keys(tmp_path):
    hist = str(tmp_path / "hist.jsonl")
    base = tmp_path / "BASELINE.json"
    base.write_text(json.dumps({"metric": "tok/s/chip",
                                "north_star": 5e4, "published": {}}))
    pl.append_records([_bench_rec(100.0)], "bench", path=hist)
    pl.publish("latest", history=hist, baseline=str(base),
               allow_cpu=True)
    data = json.loads(base.read_text())
    assert data["metric"] == "tok/s/chip"
    assert data["north_star"] == 5e4
    assert data["published"] == {"ap_tokens_per_sec": 100.0}


def test_peak_flops_table_single_source():
    """Satellite: bench.py's peak_flops_per_chip is a wrapper over the
    observatory's table — the duplicated literal is gone."""
    import bench
    from ray_tpu._private import device_stats as ds

    assert bench.peak_flops_per_chip() == ds.peak_flops_per_chip()
    src = (ROOT / "bench.py").read_text()
    assert "459e12" not in src, "bench.py regrew its own FLOPs table"


def test_device_roofline_block_shape():
    from ray_tpu._private.device_stats import device_roofline

    dev = device_roofline()
    assert dev["backend"] == "cpu"
    assert dev["peak_flops_per_chip"] == pytest.approx(1e12)
    assert dev["peak_hbm_bytes_per_sec"] == pytest.approx(1e11)
    assert dev["ridge_flops_per_byte"] == pytest.approx(10.0)


def test_engine_stats_carries_device_roofline():
    from ray_tpu.serve.telemetry import EngineTelemetry

    stats = EngineTelemetry("t_ap_roofline", max_slots=1).engine_stats()
    dev = stats["device"]
    assert dev["ridge_flops_per_byte"] == pytest.approx(10.0)
    assert dev["backend"] == "cpu"


# ---------------------------------------------------------------------------
# CLI smokes
# ---------------------------------------------------------------------------

def test_cli_attribute_from_snapshot(tmp_path, capsys):
    snap = tmp_path / "snap.json"
    snap.write_text(json.dumps({"programs": _SNAPSHOT,
                                "device": _V5E}))
    rc = ap_main(["attribute", "--snapshot", str(snap),
                  "--format", "json"])
    assert rc == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["bottleneck"] == "serve.decode"
    assert rep["device"]["device_kind"] == "TPU v5e"


def test_cli_plan_grid_on_stdout(tmp_path, capsys):
    hist = str(tmp_path / "empty.jsonl")
    rc = ap_main(["--history", hist, "plan", "--budget", "5"])
    assert rc == 0
    captured = capsys.readouterr()
    grid = json.loads(captured.out)
    assert len(grid) == 5
    # rationales go to stderr; stdout stays pure sweep_tpu argv
    assert "rationale" not in captured.out
    assert "autopilot:" in captured.err


def test_cli_subprocess_smoke():
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.tools.autopilot", "plan",
         "--budget", "2"],
        capture_output=True, text=True, cwd=str(ROOT),
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert proc.returncode == 0, proc.stderr
    grid = json.loads(proc.stdout)
    assert len(grid) == 2
