"""Trainwatch: step anatomy exact-sum, goodput, the health watchdog's
postmortem path, checkpoint accounting, and the <5% overhead guard.

The acceptance invariants this file pins (ISSUE 14):

* an injected NaN loss at step k triggers a watchdog dump WITHIN one
  step whose postmortem names the step index, trainer, and batch
  signature;
* ``train_stats()["anatomy"]`` legs sum EXACTLY to the measured step
  wall — per raw step, across jit and 8-virtual-device mesh steps
  (the same clamp-construction contract as serve's critical path);
* recording stays within 5% of the uninstrumented loop
  (``RAYTPU_TRAINWATCH=0`` early-returns), mirroring flightrec's
  guard;
* ``train_stats()`` keeps its golden schema (the dashboard
  ``/api/train/stats`` and bench ``--train`` pattern-match it).
"""

import json
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
import optax  # noqa: E402

from ray_tpu.train.goodput import (ANATOMY_COMPONENTS,  # noqa: E402
                                   GoodputTracker, dominant_component,
                                   get_goodput_tracker,
                                   get_health_watchdog,
                                   get_train_recorder,
                                   instrument_trainwatch, watch_data,
                                   worker_skew)
from ray_tpu.train.jax_trainer import jax_utils  # noqa: E402
from ray_tpu.train.telemetry import train_stats  # noqa: E402

SUMMARY_KEYS = {"count", "mean", "p50", "p95", "p99", "max"}

#: every key train_stats() promises, regardless of configuration
TOP_KEYS = {"trainer", "steps", "compiles", "examples",
            "examples_per_sec", "step_time_ms", "anatomy", "goodput",
            "health", "checkpoint", "flightrec"}

ANATOMY_KEYS = {"step_wall_ms", *ANATOMY_COMPONENTS}

GOODPUT_KEYS = {"ratio", "productive_s", "wall_s", "steps", "window"}

HEALTH_KEYS = {"observed", "anomalies", "last_anomaly", "loss",
               "grad_norm", "z_threshold", "dumps"}

CHECKPOINT_KEYS = {"saves", "restores", "bytes_written", "bytes_read",
                   "last_step", "save_ms", "restore_ms"}

FLIGHTREC_KEYS = {"enabled", "capacity", "recorded", "retained",
                  "dropped", "dumps"}


def _mse_loss(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2)


def _batches(n, seed=0, poison_at=None):
    rng = np.random.RandomState(seed)
    for i in range(n):
        batch = {"x": rng.randn(8, 4).astype(np.float32),
                 "y": rng.randn(8, 2).astype(np.float32)}
        if i == poison_at:
            batch["x"][0, 0] = np.nan
        yield batch


def _assert_exact_sum(tracker):
    steps = tracker.last_steps()
    assert steps, "no steps recorded"
    for rec in steps:
        comp_sum = sum(rec[c] for c in ANATOMY_COMPONENTS)
        assert comp_sum == pytest.approx(rec["step_wall_ms"],
                                         rel=1e-9, abs=1e-9), rec


# ---------------------------------------------------------------------------
# NaN injection -> watchdog postmortem within one step
# ---------------------------------------------------------------------------

def test_nan_loss_triggers_watchdog_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("RAYTPU_FLIGHTREC_DIR", str(tmp_path))
    name = "tw_nan"
    tx = optax.sgd(0.01)
    params = {"w": jnp.ones((4, 2))}
    step = jax_utils.build_train_step(_mse_loss, tx, health=True,
                                      telemetry_name=name)
    opt_state = tx.init(params)
    poison_at = 4
    for i, batch in enumerate(_batches(6, poison_at=poison_at)):
        params, opt_state, loss, scalars = step(params, opt_state,
                                                batch)
        wd = step.watchdog
        if i == poison_at:
            # the dump landed before the poisoned call returned —
            # detection latency is ONE step, not an epoch
            assert wd.anomalies >= 1
            assert len(wd.dumps) == 1
    doc = json.loads(open(wd.dumps[0]).read())
    ctx = doc["context"]
    assert doc["source"] == f"train:{name}"
    assert doc["reason"].startswith("train_anomaly_nonfinite")
    assert ctx["trainer"] == name
    assert ctx["step"] == poison_at
    assert ctx["signature"]          # batch signature named
    assert ctx["trail"][-1]["step"] == poison_at
    # the journal carries both the per-step trail and the anomaly
    assert doc["counts_by_kind"].get("train_step", 0) >= poison_at
    assert doc["counts_by_kind"].get("train_anomaly", 0) >= 1
    # cooldown: the second NaN step did not produce a second dump
    assert len(wd.dumps) == 1
    st = train_stats(name)
    assert st["health"]["anomalies"] >= 1
    assert st["health"]["last_anomaly"]["reason"].startswith(
        "nonfinite")
    assert st["health"]["dumps"] == wd.dumps


def test_loss_spike_detection():
    wd = get_health_watchdog("tw_spike", z_threshold=4.0)
    for i in range(20):
        assert wd.observe(i, 1.0 + 0.01 * (i % 3)) is None
    anomaly = wd.observe(20, 50.0)
    assert anomaly is not None
    assert anomaly["reason"] == "loss_spike"
    assert anomaly["metric"] == "loss"


# ---------------------------------------------------------------------------
# anatomy exact-sum: jit and 8-virtual-device mesh steps
# ---------------------------------------------------------------------------

def test_anatomy_sums_exactly_jit_step():
    name = "tw_sum_jit"
    tx = optax.sgd(0.01)
    params = {"w": jnp.ones((4, 2))}
    step = jax_utils.build_train_step(_mse_loss, tx,
                                      telemetry_name=name)
    opt_state = tx.init(params)
    it = watch_data(_batches(5), trainer=name)
    for batch in it:
        params, opt_state, loss = step(params, opt_state, batch)
    tracker = step.goodput
    _assert_exact_sum(tracker)
    st = train_stats(name)
    assert st["anatomy"]["step_wall_ms"]["count"] == 5
    # first call is the compile leg; later calls are device time
    assert st["anatomy"]["compile_ms"]["max"] > 0
    assert st["goodput"]["ratio"] is not None
    # pooled means also reconstruct the wall (same sample count).
    # summarize() rounds each mean to 0.001 ms, so the six legs and the
    # wall may each be off by half of that: the bound is the rounding's,
    # not the clock's (abs=1e-3 failed one run in five on an idle box)
    comp_mean = sum(st["anatomy"][c]["mean"]
                    for c in ANATOMY_COMPONENTS)
    assert comp_mean == pytest.approx(
        st["anatomy"]["step_wall_ms"]["mean"], rel=1e-9,
        abs=0.5e-3 * (len(ANATOMY_COMPONENTS) + 1) + 1e-9)


def test_anatomy_sums_exactly_mesh_step():
    from ray_tpu.models import (gpt2_config, gpt2_init,
                                gpt2_logical_axes, gpt2_loss)
    from ray_tpu.parallel import MeshSpec, fake_mesh

    mesh = fake_mesh(8, MeshSpec(data=4, tensor=2))
    name = "tw_sum_mesh"
    cfg = gpt2_config("nano", max_seq=32, use_flash=False,
                      dtype=jnp.float32)
    axes = gpt2_logical_axes(cfg)
    tx = optax.sgd(1e-3)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    step = jax_utils.build_train_step(
        lambda p, b: gpt2_loss(p, b, cfg), tx, mesh=mesh,
        logical_axes=axes, telemetry_name=name)
    from ray_tpu.parallel.sharding import shard_params

    rng = np.random.RandomState(0)
    # legacy mesh-context spelling (jax.set_mesh where available)
    set_mesh = getattr(jax, "set_mesh", None)
    with (set_mesh(mesh) if set_mesh is not None else mesh):
        params = shard_params(params, axes, mesh)
        opt_state = tx.init(params)
        for _ in range(3):
            batch = {"tokens": rng.randint(
                0, cfg.vocab_size, size=(4, 33)).astype(np.int32)}
            params, opt_state, loss = step(params, opt_state, batch)
    _assert_exact_sum(step.goodput)
    assert train_stats(name)["anatomy"]["step_wall_ms"]["count"] == 3


def test_data_wait_probe_attributes_input_stalls():
    name = "tw_stall"
    tracker = get_goodput_tracker(name)

    def slow_batches():
        for _ in range(4):
            time.sleep(0.02)
            yield {"x": np.zeros((2, 2), np.float32)}

    def fast_step(params, opt_state, batch):
        return params, opt_state, 0.0

    step = instrument_trainwatch(fast_step, tracker=tracker)
    params = opt_state = None
    for batch in watch_data(slow_batches(), tracker=tracker):
        params, opt_state, _ = step(params, opt_state, batch)
    _assert_exact_sum(tracker)
    st = train_stats(name)
    assert st["anatomy"]["data_wait_ms"]["p50"] >= 15.0
    assert dominant_component(st["anatomy"]) == "data_wait_ms"
    # the goodput ratio reads input-bound: almost nothing productive
    assert st["goodput"]["ratio"] < 0.5
    # ...and autopilot attribution cites it
    from ray_tpu.tools.autopilot import attribution

    rep = attribution.attribute({}, train_anatomy=st)
    assert "input-bound" in rep["summary"]
    assert rep["train_anatomy"] is st


def test_checkpoint_pause_lands_in_anatomy_and_counters(tmp_path):
    name = "tw_ckpt"
    from ray_tpu.train.checkpointing import (restore_sharded,
                                             save_sharded)

    tree = {"w": jnp.arange(12.0).reshape(3, 4)}
    target = save_sharded(tree, str(tmp_path / "ck"), step=7,
                          trainer=name)
    restored = restore_sharded(str(tmp_path / "ck"), step=7,
                               trainer=name)
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(tree["w"]))
    tracker = get_goodput_tracker(name)
    rec = tracker.record_step(0.001)   # pause drains into this step
    assert rec["checkpoint_ms"] > 0
    _assert_exact_sum(tracker)
    blk = train_stats(name)["checkpoint"]
    assert blk["saves"] == 1 and blk["restores"] == 1
    assert blk["bytes_written"] == 12 * 4
    assert blk["bytes_read"] == 12 * 4
    assert blk["last_step"] == 7
    assert blk["save_ms"]["count"] == 1
    kinds = get_train_recorder(name).counts_by_kind()
    assert kinds.get("ckpt_save") == 1
    assert kinds.get("ckpt_restore") == 1


# ---------------------------------------------------------------------------
# grad-accum steps are no longer invisible
# ---------------------------------------------------------------------------

def test_accumulated_step_instrumented_and_parity():
    from ray_tpu.train.grad_accum import accumulated_train_step

    name = "tw_accum"
    tx = optax.sgd(0.01)
    params = {"w": jnp.ones((4, 2))}
    opt_state = tx.init(params)
    batch = {"x": jnp.asarray(np.random.RandomState(0)
                              .randn(8, 4), jnp.float32),
             "y": jnp.asarray(np.random.RandomState(1)
                              .randn(8, 2), jnp.float32)}
    plain = accumulated_train_step(_mse_loss, tx, num_microbatches=4)
    wired = accumulated_train_step(_mse_loss, tx, num_microbatches=4,
                                   telemetry=True,
                                   telemetry_name=name)
    p_ref, _, loss_ref = jax.jit(plain)(params, opt_state, batch)
    p_got, _, loss_got = wired(params, opt_state, batch)
    assert float(loss_got) == pytest.approx(float(loss_ref), rel=1e-6)
    np.testing.assert_allclose(np.asarray(p_got["w"]),
                               np.asarray(p_ref["w"]), rtol=1e-6)
    wired(params, opt_state, batch)
    st = train_stats(name)
    assert st["steps"] == 2          # step-time telemetry sees it
    assert st["compiles"] >= 1       # ...and its compile event
    assert st["anatomy"]["step_wall_ms"]["count"] == 2
    _assert_exact_sum(wired.goodput)


# ---------------------------------------------------------------------------
# the jitted health path adds no host transfer
# ---------------------------------------------------------------------------

_FORBIDDEN_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                    "infeed", "outfeed", "device_put", "host_callback"}


def _prims(closed_jaxpr):
    out = set()

    def walk(jx):
        for eqn in jx.eqns:
            out.add(eqn.primitive.name)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr if hasattr(v.jaxpr, "eqns")
                         else v.jaxpr.jaxpr)

    walk(closed_jaxpr.jaxpr)
    return out


def test_health_scalars_add_no_host_transfer():
    tx = optax.sgd(0.01)
    params = {"w": jnp.ones((4, 2))}
    opt_state = tx.init(params)
    batch = {"x": jnp.zeros((8, 4)), "y": jnp.zeros((8, 2))}
    healthy = jax_utils.build_train_step(
        _mse_loss, tx, health=True, telemetry_name="tw_jaxpr")
    jaxpr = jax.make_jaxpr(healthy._raw_step)(params, opt_state, batch)
    bad = _prims(jaxpr) & _FORBIDDEN_PRIMS
    assert not bad, f"health scalars introduced host transfer: {bad}"
    # and the scalars really are step outputs, not side channels
    out = healthy(params, opt_state, batch)
    assert len(out) == 4
    scalars = jax.device_get(out[3])
    assert set(scalars) == {"loss", "grad_norm", "nonfinite"}
    assert int(scalars["nonfinite"]) == 0


# ---------------------------------------------------------------------------
# overhead guard (mirrors flightrec's)
# ---------------------------------------------------------------------------

def test_trainwatch_overhead_under_5pct(monkeypatch):
    """Recording must be cheap enough to leave on: min-of-repeats
    per-step wall with trainwatch on stays within 5% of the same step
    with RAYTPU_TRAINWATCH=0 (the wrapper early-returns).

    The step body is a fixed 5ms host wait, not a jitted matmul: on
    the 8-virtual-device CPU test rig, XLA compute itself jitters by
    more than the 5% budget, which would measure the machine, not the
    wrapper.  A deterministic-duration step isolates exactly what this
    guard is about — the wrapper's added host cost (a signature hash,
    two perf_counter reads, one locked dict append; ~10-50us) against
    a representative ms-scale train-step wall."""
    batch = {"x": np.zeros((4, 4), np.float32)}

    def fenced_step(params, opt_state, b):
        time.sleep(0.005)

    # enabled is latched at tracker construction, so build both
    # wrappers first, then interleave the timed blocks — per-step
    # minimum per arm, so machine drift hits both arms equally
    monkeypatch.setenv("RAYTPU_TRAINWATCH", "0")
    off_step = instrument_trainwatch(
        fenced_step, tracker=GoodputTracker("tw_ovr_off"))
    monkeypatch.setenv("RAYTPU_TRAINWATCH", "1")
    on_step = instrument_trainwatch(
        fenced_step, tracker=GoodputTracker("tw_ovr_on"))

    def min_step(step, n=30):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            step(None, None, batch)
            best = min(best, time.perf_counter() - t0)
        return best

    min_step(off_step, 3), min_step(on_step, 3)   # wrapper warmup
    off = min(min_step(off_step) for _ in range(3))
    on = min(min_step(on_step) for _ in range(3))
    assert on <= off * 1.05, (on, off)


# ---------------------------------------------------------------------------
# golden schema
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("stepped", [False, True],
                         ids=["fresh", "stepped"])
def test_train_stats_schema(stepped, tmp_path, monkeypatch):
    monkeypatch.setenv("RAYTPU_FLIGHTREC_DIR", str(tmp_path))
    name = f"tw_schema_{'stepped' if stepped else 'fresh'}"
    if stepped:
        tx = optax.sgd(0.01)
        params = {"w": jnp.ones((4, 2))}
        step = jax_utils.build_train_step(_mse_loss, tx, health=True,
                                          telemetry_name=name)
        opt_state = tx.init(params)
        for batch in _batches(3):
            params, opt_state, _, _ = step(params, opt_state, batch)
    stats = train_stats(name)
    missing = TOP_KEYS - set(stats)
    assert not missing, f"train_stats() lost keys: {missing}"
    assert set(stats["anatomy"]) == ANATOMY_KEYS
    for comp in stats["anatomy"].values():
        assert set(comp) == SUMMARY_KEYS
    assert set(stats["goodput"]) == GOODPUT_KEYS
    assert set(stats["health"]) == HEALTH_KEYS
    for m in ("loss", "grad_norm"):
        assert set(stats["health"][m]) == {"last", "ewma", "ewma_std"}
    assert set(stats["checkpoint"]) == CHECKPOINT_KEYS
    assert set(stats["checkpoint"]["save_ms"]) == SUMMARY_KEYS
    assert set(stats["flightrec"]) == FLIGHTREC_KEYS
    assert set(stats["step_time_ms"]) == SUMMARY_KEYS
    if stepped:
        assert stats["anatomy"]["step_wall_ms"]["count"] == 3
        assert stats["goodput"]["steps"] == 3
        assert stats["health"]["observed"] == 3
        assert stats["flightrec"]["recorded"] >= 3
    else:
        assert stats["anatomy"]["step_wall_ms"]["count"] == 0
        assert stats["goodput"]["ratio"] is None
        assert stats["health"]["observed"] == 0


# ---------------------------------------------------------------------------
# multi-worker skew
# ---------------------------------------------------------------------------

def test_worker_skew_flags_stragglers():
    rep = worker_skew({"w0": 100.0, "w1": 104.0, "w2": 98.0,
                       "w3": 210.0})
    assert rep["workers"] == 4
    assert rep["stragglers"] == ["w3"]
    assert rep["spread"] > 1.0
    even = worker_skew({"w0": 100.0, "w1": 101.0})
    assert even["stragglers"] == []
    # 2-worker fleet, one 2x slower: the even-count median must not
    # BE the straggler (true median, not upper-middle)
    two = worker_skew({"w0": 100.0, "w1": 200.0})
    assert two["stragglers"] == ["w1"]
    assert worker_skew({})["workers"] == 0


# ---------------------------------------------------------------------------
# postmortem CLI renders the train lanes
# ---------------------------------------------------------------------------

def test_flightrec_report_renders_train_lanes():
    from ray_tpu.tools.flightrec import report_lines, sweepjson_records

    doc = {
        "version": 1, "source": "train:t0",
        "reason": "train_anomaly_nonfinite_loss",
        "created": "2026-08-06T00:00:00", "uptime_s": 2.0,
        "events_recorded": 5, "events_retained": 5,
        "events_dropped": 0,
        "counts_by_kind": {"train_step": 3, "train_anomaly": 1,
                           "ckpt_save": 1},
        "context": {"trainer": "t0", "step": 2,
                    "reason": "nonfinite_loss", "metric": "loss",
                    "value": "nan",
                    "trail": [{"step": 1, "loss": 0.5},
                              {"step": 2, "loss": "nan"}]},
        "events": [
            {"seq": 1, "t_s": 0.1, "kind": "train_step", "step": 0,
             "loss": 0.7, "wall_ms": 12.0},
            {"seq": 2, "t_s": 0.2, "kind": "train_step", "step": 1,
             "loss": 0.5, "wall_ms": 11.0},
            {"seq": 3, "t_s": 0.25, "kind": "ckpt_save", "step": 1,
             "dur_ms": 4.0, "bytes": 48},
            {"seq": 4, "t_s": 0.3, "kind": "train_step", "step": 2,
             "loss": "nan", "wall_ms": 13.0},
            {"seq": 5, "t_s": 0.3, "kind": "train_anomaly", "step": 2,
             "reason": "nonfinite_loss", "metric": "loss",
             "value": "nan"},
        ],
    }
    text = "\n".join(report_lines(doc))
    assert "train steps: n=3" in text
    assert "train anomalies" in text
    assert "2  loss  nan  nonfinite_loss" in text
    assert "trainer=t0" in text
    assert "ckpt_save" in text
    assert "metric trail" in text
    recs = sweepjson_records(doc)
    assert any(r["metric"] == "flightrec_train_anomaly_events"
               and r["value"] == 1 for r in recs)


# ---------------------------------------------------------------------------
# perfledger direction for the new metrics
# ---------------------------------------------------------------------------

def test_perfledger_goodput_direction():
    from ray_tpu.tools.perfledger import (_SWEEP_FIELDS,
                                          higher_is_better)

    assert higher_is_better("train_goodput")
    assert not higher_is_better("train_data_wait_ms_p99")
    for f in ("train_goodput", "train_data_wait_ms_p50",
              "train_data_wait_ms_p99"):
        assert f in _SWEEP_FIELDS
