"""Healthwatch acceptance: liveness state machine, stall detection,
chaos fault injection, death-requeue, and incident timelines.

Host-only units drive :class:`HealthMonitor` with injected clocks
(HEALTHY→SUSPECT→DEAD thresholds, idle immunity, fault-stamped
detection latency, stall dedup, probe throttling) and pin the chaos
injector's wave arithmetic.  The end-to-end scenario freezes one
replica of a live two-replica fleet mid-traffic and demands the full
story: the monitor catches it within ``dead_ms``, the router requeues
its stranded queue and routes around it, every request still matches
the dense single-engine oracle bit-for-bit, and the incidents CLI
names the sick replica, its detection latency, and the SLO burn
window from one tracebus dump.  A final interleaved min-of-5 guard
bounds healthwatch's chaos-free hot-path overhead under 5%.
"""

import asyncio
import json
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.serve.chaos import ChaosConfig, ChaosInjector  # noqa: E402
from ray_tpu.serve.health import (DEAD, HEALTHY, SUSPECT,  # noqa: E402
                                  HealthConfig, HealthMonitor,
                                  empty_fleet_health, empty_health,
                                  healthwatch_enabled)
from ray_tpu.serve.router import build_llm_fleet  # noqa: E402
from ray_tpu.serve.slo import SLOConfig  # noqa: E402

MAX_NEW = 6
_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}
_ENGINE_KW = dict(max_new_tokens=MAX_NEW, temperature=0.0,
                  kv_block_size=16, prefill_bucket=16, max_slots=2,
                  config_overrides=_OVR)


class _Recorder:
    """Journal stand-in: keeps every record as a plain dict."""

    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append(dict(fields, kind=kind))

    def kinds(self):
        return [e["kind"] for e in self.events]


def _monitor(rec=None, **cfg_kw):
    cfg = HealthConfig(**{**dict(suspect_ms=30.0, dead_ms=90.0,
                                 stall_ms=50.0, probe_ms=0.0),
                          **cfg_kw})
    return HealthMonitor(cfg, deployment="t_hw", recorder=rec,
                         enabled=True, now=0.0)


# ---------------------------------------------------------------------------
# state machine units (host-only, injected clocks)
# ---------------------------------------------------------------------------

def test_state_machine_suspect_dead_recover_cycle():
    rec = _Recorder()
    m = _monitor(rec)
    m.register("r0", now=0.0)
    m.heartbeat("r0", now=0.0)
    # fresh heartbeat: nothing to report
    assert m.probe(now=0.01) == []
    assert m.state("r0") == HEALTHY
    # stale past suspect_ms
    [tr] = m.probe(now=0.05)
    assert tr["to"] == SUSPECT and tr["reason"] == "heartbeat_stale"
    assert m.state("r0") == SUSPECT
    # stale past dead_ms
    [tr] = m.probe(now=0.10)
    assert tr["to"] == DEAD and tr["reason"] == "heartbeat_lost"
    assert m.state("r0") == DEAD
    # dead stays dead: no duplicate transitions on further sweeps
    assert m.probe(now=0.20) == []
    # the loop comes back: recovery on the next heartbeat
    m.heartbeat("r0", now=0.25)
    assert m.state("r0") == HEALTHY

    blk = m.replica_block("r0", now=0.25)
    assert blk["enabled"] is True
    assert blk["suspect_count"] == 1 and blk["dead_count"] == 1
    assert blk["recoveries"] == 1 and blk["transitions"] == 3
    assert [t["to"] for t in blk["transition_log"]] == \
        [SUSPECT, DEAD, HEALTHY]
    kinds = rec.kinds()
    assert kinds.count("health_transition") == 3
    assert rec.events[-1]["to"] == HEALTHY
    assert rec.events[-1]["reason"] == "heartbeat_resumed"


def test_idle_replicas_are_never_suspected():
    m = _monitor()
    m.register("r0", now=0.0)
    # replicas register idle: stale-by-hours is not a failure
    assert m.probe(now=10.0) == []
    m.heartbeat("r0", now=10.0)
    m.note_idle("r0", now=10.5)
    # parked with no work: still immune however old the stamp gets
    assert m.probe(now=20.0) == []
    assert m.state("r0") == HEALTHY
    # the next heartbeat re-arms the staleness clock
    m.heartbeat("r0", now=20.0)
    out = m.probe(now=20.2)
    assert [t["to"] for t in out] == [DEAD]


def test_detection_latency_measured_from_fault_instant():
    rec = _Recorder()
    m = _monitor(rec)
    m.register("r0", now=0.0)
    m.heartbeat("r0", now=0.0)
    m.note_fault("r0", kind="freeze", now=0.02)
    [s] = m.probe(now=0.05)
    assert s["to"] == SUSPECT
    [d] = m.probe(now=0.12)
    assert d["to"] == DEAD
    # fault stamped at 20ms, DEAD at 120ms -> 100ms to detect
    assert d["time_to_detect_ms"] == pytest.approx(100.0)
    assert m.time_to_detect_ms == pytest.approx(100.0)
    blk = m.fleet_block(now=0.12)
    assert blk["time_to_detect_ms"] == pytest.approx(100.0)
    assert blk["faults_injected"] == 1
    assert rec.kinds()[0] == "fault_injected"
    assert rec.events[0]["fault"] == "freeze"


class _StallTele:
    """EngineTelemetry stand-in for the stall sweep."""

    def __init__(self):
        self.stalls = []

    def stalled_requests(self, stall_ms, now=None):
        return list(self.stalls)


def test_stall_sweep_suspects_replica_once_per_request():
    rec = _Recorder()
    m = _monitor(rec)
    rrec = _Recorder()
    tele = _StallTele()
    m.register("r0", recorder=rrec, telemetry=tele, now=0.0)
    m.heartbeat("r0", now=0.0)
    tele.stalls = [{"id": "q-1", "silent_ms": 70.0, "trace": None}]
    out = m.probe(now=0.01)
    assert [t["to"] for t in out] == [SUSPECT]
    assert out[0]["reason"] == "request_stall"
    # the same stalled request again: no duplicate journal entry and
    # no second transition
    assert m.probe(now=0.02) == []
    stalls = [e for e in rrec.events if e["kind"] == "request_stall"]
    assert len(stalls) == 1 and stalls[0]["req"] == "q-1"
    assert "trace" not in stalls[0]  # None trace never journaled
    # the fleet recorder got its copy of the stall too
    assert rec.kinds().count("request_stall") == 1
    assert m.replica_block("r0", now=0.02)["stalls"] == 1


def test_maybe_probe_throttles_by_probe_ms():
    m = _monitor(probe_ms=50.0)
    m.register("r0", now=0.0)
    m.heartbeat("r0", now=0.0)
    assert m.maybe_probe(now=0.0) == []  # arms the window
    # inside the window: no sweep, even though the beat is now stale
    assert m.maybe_probe(now=0.04) == []
    assert m.state("r0") == HEALTHY
    # past the window: the sweep runs and suspects
    out = m.maybe_probe(now=0.06)
    assert [t["to"] for t in out] == [SUSPECT]


def test_disabled_monitor_is_inert():
    m = HealthMonitor(HealthConfig(suspect_ms=1.0, dead_ms=2.0),
                      deployment="t_off", enabled=False)
    m.register("r0")
    m.heartbeat("r0")
    m.note_fault("r0")
    assert m.probe(now=99.0) == []
    assert m.maybe_probe(now=99.0) == []
    assert m.state("r0") == HEALTHY
    assert m.replica_block("r0") == empty_health()
    assert m.fleet_block() == empty_fleet_health()
    assert m.time_to_detect_ms is None


def test_kill_switch_env(monkeypatch):
    monkeypatch.setenv("RAYTPU_HEALTHWATCH", "0")
    assert not healthwatch_enabled()
    assert HealthMonitor(deployment="t_env").enabled is False
    monkeypatch.setenv("RAYTPU_HEALTHWATCH", "1")
    assert healthwatch_enabled()
    assert HealthMonitor(deployment="t_env").enabled is True


# ---------------------------------------------------------------------------
# chaos injector units
# ---------------------------------------------------------------------------

def test_chaos_config_validation():
    for bad in (dict(freeze_poll_ms=0.0), dict(freeze_waves=-1),
                dict(freeze_after_waves=-1), dict(delay_token_ms=-1.0),
                dict(delay_token_waves=-1), dict(drop_handoff_nth=-1)):
        with pytest.raises(ValueError):
            ChaosConfig(**bad)


def test_default_chaos_config_arms_nothing():
    cfg = ChaosConfig()
    assert not cfg.any_faults()
    inj = ChaosInjector(cfg)
    inj.bind("f/r0")
    assert not any(inj.frozen("f/r0") for _ in range(50))
    assert inj.token_delay_s("f/r0") == 0.0
    assert not inj.should_drop_handoff()
    st = inj.stats()
    assert st["armed"] is False
    assert st["frozen_polls"] == {} and st["dropped_handoffs"] == 0


def test_chaos_freeze_window_and_single_fault_stamp():
    rec = _Recorder()
    m = _monitor(rec)
    m.register("f/r1", now=0.0)
    m.heartbeat("f/r1", now=0.0)
    inj = ChaosInjector(ChaosConfig(freeze_replica=1,
                                    freeze_after_waves=2,
                                    freeze_waves=3), monitor=m)
    inj.bind("f/r0")
    inj.bind("f/r1")
    # the untargeted replica never freezes (index targeting is by
    # bind order)
    assert not any(inj.frozen("f/r0") for _ in range(10))
    # victim: 2 real waves, 3 frozen poll windows, then thaw for good
    assert [inj.frozen("f/r1") for _ in range(7)] == \
        [False, False, True, True, True, False, False]
    assert inj.stats()["frozen_polls"] == {"f/r1": 3}
    # the fault instant was stamped on the monitor exactly once
    faults = [e for e in rec.events if e["kind"] == "fault_injected"]
    assert len(faults) == 1
    assert faults[0]["replica"] == "f/r1"
    assert faults[0]["fault"] == "freeze"


def test_chaos_token_delay_budget_and_handoff_drop_counter():
    inj = ChaosInjector(ChaosConfig(delay_token_replica="f/r0",
                                    delay_token_ms=4.0,
                                    delay_token_waves=2,
                                    drop_handoff_nth=2))
    inj.bind("f/r0")
    assert inj.token_delay_s("f/r0") == pytest.approx(0.004)
    assert inj.token_delay_s("f/r0") == pytest.approx(0.004)
    assert inj.token_delay_s("f/r0") == 0.0  # wave budget spent
    assert inj.token_delay_s("f/other") == 0.0
    # exactly the Nth (1-based) package drops
    assert [inj.should_drop_handoff() for _ in range(4)] == \
        [False, True, False, False]
    assert inj.dropped_handoffs == 1


def test_perfledger_tracks_detection_latency_lower_is_better():
    from ray_tpu.tools.perfledger import _SWEEP_FIELDS, higher_is_better

    assert "time_to_detect_ms" in _SWEEP_FIELDS
    assert not higher_is_better("time_to_detect_ms")


# ---------------------------------------------------------------------------
# end-to-end: frozen replica detected, routed around, oracle-identical
# ---------------------------------------------------------------------------

def _oracle(prompt, max_new=MAX_NEW):
    """Dense solo greedy continuation — the parity reference."""
    from ray_tpu.models import gpt2_config, gpt2_init
    from ray_tpu.models.gpt2_decode import generate

    cfg = gpt2_config("nano", **_OVR)
    params = gpt2_init(jax.random.PRNGKey(0), cfg)
    out = generate(params, jnp.asarray(np.asarray(prompt)[None]), cfg,
                   max_new_tokens=max_new, temperature=0.0)
    return np.asarray(out)[0]


def test_chaos_freeze_detected_requeued_and_oracle_identical(
        tmp_path, capsys, monkeypatch):
    # a one-chip host: both replicas share device 0 and its compiled
    # programs.  On distinct devices each replica stalls in its own
    # first compile, which the thresholds below read as deaths of
    # their own, before and between the injected one.
    one_chip = jax.local_devices()[:1]
    monkeypatch.setattr(jax, "local_devices", lambda: one_chip)
    rng = np.random.RandomState(7)
    # fixed-length prompts: the oracle's generate jit compiles once
    prompts = [rng.randint(2, 500, 24).astype(np.int32)
               for _ in range(12)]

    # A stale replica is SUSPECT from 30 ms and DEAD from a second.
    # The window between the two is what must not depend on the box: a
    # sweep (one a ping below, 20 ms apart on an idle box) that finds
    # the frozen replica's beat already older than dead_ms takes it
    # from HEALTHY straight to DEAD.  With dead_ms at 90 the window
    # was 60 ms, and beside a busy neighbour two sweeps lie further
    # apart than that: SUSPECT was skipped in every run.  The freeze
    # outlasts dead_ms whatever the box does (500 polls of >= 5 ms).
    health = HealthConfig(suspect_ms=30.0, dead_ms=1000.0,
                          stall_ms=60_000.0, probe_ms=1.0)
    chaos = ChaosConfig(seed=0, freeze_replica=1, freeze_after_waves=2,
                        freeze_waves=500, freeze_poll_ms=5.0)
    # unreachable-fast TTFT target: the freeze window burns the SLO,
    # giving the incident report a burn window to name
    slo = SLOConfig(ttft_ms=5.0, e2e_ms=600_000.0, objective=0.5,
                    dump_on_breach=False)
    # inflight cap above max_slots so the frozen replica's engine
    # queue holds not-yet-admitted requests for the router to rescue
    fleet = build_llm_fleet(
        "gpt2", "nano", fleet_name="t_chaos", num_replicas=2,
        routing="round_robin", wfq=False, slo=slo, health=health,
        chaos=chaos, max_inflight_per_replica=6, **_ENGINE_KW)
    frozen_name = "t_chaos/r1"

    async def main():
        tasks = [asyncio.create_task(fleet(p)) for p in prompts]
        # keep the healthy replica's pump busy until detection fires:
        # every submit runs the router's health sweep, and the pings
        # themselves route around the sick replica
        pings = []
        deadline = time.perf_counter() + 60.0
        while (fleet.health.time_to_detect_ms is None
               and time.perf_counter() < deadline):
            pings.append(asyncio.create_task(fleet(prompts[0])))
            await asyncio.sleep(0.02)
        outs = await asyncio.gather(*tasks)
        pouts = await asyncio.gather(*pings)
        return outs, pouts

    try:
        outs, pouts = asyncio.run(main())

        # detection: the frozen replica went SUSPECT then DEAD, and
        # the latency is measured from the chaos fault instant
        fs = fleet.fleet_stats()
        hb = fs["health"]
        assert hb["enabled"] is True
        assert hb["faults_injected"] >= 1
        assert hb["chaos"]["armed"] is True
        assert hb["chaos"]["frozen_polls"].get(frozen_name, 0) > 0
        ttd = hb["time_to_detect_ms"]
        assert ttd is not None and 0 < ttd < 60_000.0
        rep_blk = hb["replicas"][frozen_name]
        assert rep_blk["time_to_detect_ms"] == ttd
        tos = [t["to"] for t in rep_blk["transitions"]]
        assert SUSPECT in tos and DEAD in tos
        # the loop thawed and heartbeat: nobody is dead at the end
        assert hb["by_state"][DEAD] == 0

        # rescue: the dead replica's queued (not-yet-admitted)
        # requests were push_front-requeued to the healthy replica
        assert hb["requeued_on_death"] >= 1
        assert fs["router"]["requeued_on_death"] == \
            hb["requeued_on_death"]

        # semantics: chaos + requeue never change results — every
        # request is bit-identical to the dense greedy oracle
        for p, o in zip(prompts, outs):
            np.testing.assert_array_equal(o, _oracle(p))
        ping_oracle = _oracle(prompts[0])
        for o in pouts:
            np.testing.assert_array_equal(o, ping_oracle)

        # one tracebus dump carries every lane the incident spans
        from ray_tpu.tools import incidents, tracebus

        dump_path = str(tmp_path / "chaos_dump.json")
        tracebus.write_dump(tracebus.collect(fleet), dump_path)
    finally:
        fleet.shutdown()

    doc = incidents.load(dump_path)
    events = incidents.merge_events(doc)
    incs = incidents.extract_incidents(events)
    inc = next(i for i in incs if i["replica"] == frozen_name)
    assert inc["fault_kind"] == "freeze"
    assert inc["suspect_t"] is not None and inc["dead_t"] is not None
    assert inc["time_to_detect_ms"] == pytest.approx(ttd)
    assert inc["requeued"] == hb["requeued_on_death"]
    assert incidents.burn_windows(events), "no SLO burn window found"

    # the CLI report names the sick replica, its detection latency,
    # and the burn window
    assert incidents.main(["report", dump_path]) == 0
    text = capsys.readouterr().out
    assert frozen_name in text
    assert "fault injected: freeze" in text
    assert "time_to_detect_ms=" in text
    assert "slo burn window" in text
    assert "requeued_on_death=" in text

    # timeline: merged chronological stream mentions the transitions
    assert incidents.main(["timeline", dump_path]) == 0
    text = capsys.readouterr().out
    assert "health_transition" in text and "fault_injected" in text

    # export: a chrome-trace incident lane at pid 95
    trace_path = str(tmp_path / "incidents_trace.json")
    assert incidents.main(
        ["export", dump_path, "-o", trace_path]) == 0
    capsys.readouterr()
    with open(trace_path) as f:
        trace = json.load(f)
    assert any(e.get("ph") == "i" and e.get("pid") == 95
               for e in trace)


def test_flightrec_report_renders_health_lane(tmp_path):
    """The flightrec CLI's postmortem report grows a health lane:
    per-replica transition/stall counts from the journaled stream."""
    from ray_tpu._private.flightrec import FlightRecorder
    from ray_tpu.tools.flightrec import load_dump, report_lines

    fr = FlightRecorder("t_lane", capacity=64)
    m = HealthMonitor(HealthConfig(suspect_ms=30.0, dead_ms=90.0),
                      deployment="t_lane", recorder=fr, enabled=True)
    m.register("t_lane/r0", now=0.0)
    m.heartbeat("t_lane/r0", now=0.0)
    m.note_fault("t_lane/r0", kind="freeze", now=0.01)
    m.probe(now=0.05)
    m.probe(now=0.12)
    m.heartbeat("t_lane/r0", now=0.2)
    fr.dump_dir = str(tmp_path)
    path = fr.dump(reason="test/health_lane")
    text = "\n".join(report_lines(load_dump(path)))
    assert "health transitions (by replica):" in text
    assert "t_lane/r0" in text


# ---------------------------------------------------------------------------
# traffic harness carries the detection headlines
# ---------------------------------------------------------------------------

def test_traffic_report_carries_detection_headlines():
    from ray_tpu.serve.traffic import TrafficSpec, run_traffic_fleet

    spec = TrafficSpec(num_requests=10, seed=3, rate_rps=500.0,
                       num_prefix_groups=2, prefix_len=32,
                       p_shared=0.5, tail_len_mean=4.0, tail_len_max=8,
                       vocab=500)
    rep = run_traffic_fleet(
        spec, num_replicas=2, max_slots=2, max_new_tokens=4,
        prefill_bucket=16, time_scale=0.0, routing="round_robin",
        wfq=False, config_overrides=_OVR,
        health=HealthConfig(suspect_ms=30.0, dead_ms=90.0,
                            stall_ms=60_000.0, probe_ms=1.0),
        chaos=ChaosConfig(freeze_replica=1, freeze_after_waves=2,
                          freeze_waves=100, freeze_poll_ms=5.0),
        max_inflight_per_replica=5)
    # the flattened healthwatch headlines are always present
    assert "time_to_detect_ms" in rep
    assert isinstance(rep["requests_requeued_on_death"], int)
    hb = rep["fleet"]["health"]
    assert hb["enabled"] is True
    assert hb["faults_injected"] >= 1
    assert hb["chaos"]["frozen_polls"]
    assert rep["completed"] + rep["shed"] == rep["offered"]


# ---------------------------------------------------------------------------
# overhead + inertness guards
# ---------------------------------------------------------------------------

def test_healthwatch_overhead_under_five_percent_and_chaos_inert(
        per_call_us):
    rng = np.random.RandomState(9)
    prompts = [rng.randint(2, 500, 24).astype(np.int32)
               for _ in range(6)]
    # generous thresholds: no transitions fire, so the measurement is
    # the pure hot-path cost (heartbeat + throttled probe per wave)
    fleet = build_llm_fleet(
        "gpt2", "nano", fleet_name="t_ovh", num_replicas=1,
        routing="round_robin", wfq=False,
        health=HealthConfig(suspect_ms=60_000.0, dead_ms=120_000.0,
                            stall_ms=60_000.0, probe_ms=1.0),
        **_ENGINE_KW)

    # chaos hooks provably inert when unset: nothing attached anywhere
    assert fleet.chaos is None
    for rep in fleet._replicas:
        assert rep.inst._chaos is None
    assert "chaos" not in fleet.fleet_stats()["health"]
    monitor = fleet.health
    assert monitor is not None

    def _arm(on):
        fleet.router._health = monitor if on else None
        for rep in fleet._replicas:
            rep.inst._health = monitor if on else None

    label = fleet._replicas[0].inst._replica_label

    async def main():
        outs = await asyncio.gather(*[fleet(p) for p in prompts])
        armed = monitor.replica_block(label)["heartbeats"]
        _arm(False)
        await asyncio.gather(*[fleet(p) for p in prompts])
        disarmed = monitor.replica_block(label)["heartbeats"]
        _arm(True)
        return outs, armed, disarmed

    try:
        outs, armed, disarmed = asyncio.run(main())
        stats = fleet.fleet_stats()["health"]
    finally:
        fleet.shutdown()
    # armed, the engine's loop heartbeats every wave and no transition
    # fires under the generous thresholds; disarmed, the same waves
    # make no call into the monitor at all
    assert len(outs) == len(prompts)
    assert armed > 0 and disarmed == armed
    assert stats["replicas"][label]["state"] == "healthy"

    # the pure hot-path cost, a budget per call measured in isolation
    # (timeit, min of repeats): one heartbeat and one throttled probe a
    # wave.  5% of even a 1 ms wave leaves 50 us; the two must stay
    # under 5 and 20.  (This used to be a wall-clock A/B of armed and
    # disarmed waves, which six xdist workers on shared cores decide,
    # not the monitor: it failed under load in PR 25's runs.)
    beat = per_call_us(lambda: monitor.heartbeat(label))
    probe = per_call_us(monitor.maybe_probe)
    assert beat < 5.0, f"heartbeat() costs {beat:.2f} us a call"
    assert probe < 20.0, f"maybe_probe() costs {probe:.2f} us a call"
