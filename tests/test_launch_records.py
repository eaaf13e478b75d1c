"""Every program the continuous engine hands the device has one launch
record (serve/engine.py `LLMEngine._launch`, serve/telemetry.py
`EngineTelemetry.record_launch`): an engine-wide ``seq``, the program's
name as a trace prints it, the rows it steps or stalls, its request and
bucket where it is a prefill, the launches in flight ahead of it, and
the stamps of its dispatch and fence phases.  The dispatch and fence
spans carry the ``seq`` on the profiler's clock, the records outlive
the engine (`recent_launches`), and ``engine_stats()["launches"]`` and
``["hold"]`` are their sums."""

import asyncio
import glob

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private.device_stats import get_registry  # noqa: E402
from ray_tpu.models.decode_common import SamplingParams  # noqa: E402
from ray_tpu.serve import telemetry  # noqa: E402
from ray_tpu.serve.engine import _IN_FLIGHT  # noqa: E402
from ray_tpu.serve.llm import SpecConfig, build_llm_deployment  # noqa: E402
from ray_tpu.serve.router import build_llm_fleet  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}
MAX_NEW = 6
BLOCK = 16
#: the registry's name of a program -> the name a trace gives it, which
#: is the name its launch records carry
TRACE_NAME = {"serve.prefill": "jit_prefill_sample",
              "serve.paged_prefill": "jit_paged_prefill_sample",
              "serve.decode": "jit_pool_step",
              "serve.spec_verify": "jit_verify"}
LAYOUTS = {
    "paged": dict(kv_layout="paged"),
    "dense": dict(kv_layout="dense"),
    "paged_chunked": dict(kv_layout="paged", prefill_chunk_tokens=16),
    "paged_spec": dict(kv_layout="paged",
                       spec_decode=SpecConfig(draft="ngram", k=2)),
}


class _Fixed(list):
    """What the last waves took, held still."""

    def append(self, took):
        pass


def _engine(**kw):
    args = dict(scheduler="continuous", kv_layout="paged",
                prefill_bucket=16, max_slots=3, max_new_tokens=MAX_NEW,
                temperature=0.0, config_overrides=_OVR)
    args.update(kw)
    if args["kv_layout"] == "paged":
        args.setdefault("kv_block_size", BLOCK)
    return build_llm_deployment("gpt2", "nano", **args).func_or_class()


def _prompts(lens=(7, 19, 33, 12, 40), seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 500, n).astype(np.int32) for n in lens]


def _calls() -> dict:
    """Calls of each instrumented serving program so far, by the name a
    trace gives it (the registry is the process's)."""
    snap = get_registry().snapshot(prefix="serve.")
    return {TRACE_NAME[name]: block["invokes"] + block["compile_events"]
            for name, block in snap.items() if name in TRACE_NAME}


def _drive(inst, prompts, step_s=None, gap_waves=0, sampling=None):
    """Answers of `prompts` sent together (or `gap_waves` waves apart),
    the engine's stats and its request records; `step_s` is what the
    engine is told every wave takes (a millisecond: it runs ahead)."""
    async def main():
        if step_s is not None:
            inst._wave_s = _Fixed([step_s])
        waves = [0]
        wave = inst._wave

        def counted():
            waves[0] += 1
            wave()

        inst._wave = counted
        busy = [0]

        async def one(i, p):
            while waves[0] < gap_waves * i and busy[0]:
                await asyncio.sleep(0)
            busy[0] += 1
            try:
                return await inst(p, sampling and sampling.get(i))
            finally:
                busy[0] -= 1

        try:
            outs = await asyncio.wait_for(asyncio.gather(
                *[one(i, p) for i, p in enumerate(prompts)]), 300)
            return outs, inst.engine_stats(), inst.trace_records()
        finally:
            inst.shutdown_engine()

    return asyncio.run(main())


# ------------------------------------------------------- one per launch

@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_program_launched_has_one_record(layout):
    inst = _engine(**LAYOUTS[layout])
    before = _calls()
    outs, stats, _ = _drive(inst, _prompts(), step_s=1.0)
    assert all(len(o) for o in outs)
    records = inst.launch_records()
    seqs = [r["seq"] for r in records]
    # engine-wide, from one, and landed in the order they were made
    assert seqs == list(range(1, len(seqs) + 1))
    made = {p: n - before.get(p, 0) for p, n in _calls().items()
            if n - before.get(p, 0)}
    by_program = {}
    for r in records:
        by_program[r["program"]] = by_program.get(r["program"], 0) + 1
    assert by_program == made
    kinds = {r["kind"] for r in records}
    assert kinds == {"paged": {"prefill", "decode"},
                     "dense": {"prefill", "decode"},
                     "paged_chunked": {"prefill", "chunk", "decode"},
                     "paged_spec": {"prefill", "spec"}}[layout]
    for r in records:
        assert not set(r) & set(_IN_FLIGHT)    # the device is let go
        assert r["kind"] in telemetry.LAUNCH_KINDS
        d0, d1 = r["dispatch"]
        f0, f1 = r["fence"]
        assert 0.0 < d0 <= d1 and d0 <= f0 <= f1
        # one phase holds both halves, and says so
        assert (r["dispatch"] == r["fence"]) == bool(r.get("fused"))
        assert bool(r.get("fused")) == (r["kind"] in ("chunk", "spec"))


def test_a_mixed_step_is_one_fused_record_of_the_logits_program():
    inst = _engine()
    hot = SamplingParams(temperature=0.7, top_k=5)
    _drive(inst, _prompts((9, 21)), step_s=1.0, sampling={1: hot})
    records = inst.launch_records()
    mixed = [r for r in records if r["kind"] == "mixed"]
    assert mixed and all(r["program"] == "jit_pool_logits" and r["fused"]
                         and r["dispatch"] == r["fence"] for r in mixed)
    # the overriding request's prefill ran the logits twin
    assert {r["program"] for r in records if r["kind"] == "prefill"} == {
        "jit_paged_prefill_sample", "jit_paged_prefill_raw"}
    assert [r["seq"] for r in records] == list(range(1, len(records) + 1))


def test_a_handoff_admission_is_a_launch_of_the_decode_replica():
    prompts = _prompts((7, 19))
    fleet = build_llm_fleet(
        "gpt2", "nano", fleet_name="t_launch_handoff",
        num_prefill_replicas=1, num_decode_replicas=1,
        max_new_tokens=MAX_NEW, temperature=0.0, kv_block_size=BLOCK,
        prefill_bucket=16, max_slots=2, config_overrides=_OVR)

    async def main():
        try:
            await asyncio.wait_for(
                asyncio.gather(*[fleet(p) for p in prompts]), 300)
            return {r.role: r.inst.launch_records()
                    for r in fleet.router.live_replicas}
        finally:
            fleet.shutdown()

    by_role = asyncio.run(main())
    spliced = [r for r in by_role["decode"] if r["kind"] == "handoff"]
    assert len(spliced) == len(prompts)
    assert all(r["program"] == "jit_kv_handoff_install" and r["fused"]
               and r["fence"][0] <= r["fence"][1] for r in spliced)
    assert {r["kind"] for r in by_role["prefill"]} == {"prefill"}
    # the decode replica prefills nothing
    assert {r["kind"] for r in by_role["decode"]} == {"handoff", "decode"}


# ------------------------------------------------ what a record says

def test_records_agree_with_the_request_records():
    inst = _engine()
    prompts = _prompts((7, 19, 33, 12))
    prompts.append(prompts[2].copy())       # a prefix hit: 32 resident
    _, stats, requests = _drive(inst, prompts, step_s=1.0)
    records = inst.launch_records()
    by_id = {r["id"]: r for r in requests}
    prefills = [r for r in records if r["kind"] == "prefill"]
    assert sorted(r["req"] for r in prefills) == sorted(by_id)
    for r in prefills:
        req = by_id[r["req"]]
        assert r["bucket"] == req["bucket"]
        assert r["prefix_len"] + r["n_tail"] == req["prompt_len"]
        assert r["prefix_len"] == req["kv_reserve"][3] * BLOCK
        assert r["bucket"] == -(-r["n_tail"] // 16) * 16
        # rows that stood behind it: never itself, never past the pool
        assert 0 <= r["rows"] < 3
        # it was dispatched after its admission and fenced by the
        # stamp the request's first token carries
        assert req["admit"] <= r["dispatch"][0]
        assert r["fence"][1] <= req["first_token"]
    assert any(r["prefix_len"] == 32 and r["n_tail"] == 1
               for r in prefills)
    # nothing runs ahead at a second a wave: a wave steps the rows
    # that get its tokens, and the first token is the prefill's
    waves = [r for r in records if r["kind"] == "decode"]
    assert sum(r["rows"] for r in waves) \
        == sum(req["tokens"] - 1 for req in requests)
    assert all(1 <= r["rows"] <= 3 and r["ahead"] == 0 for r in waves)
    assert all("req" not in r and "bucket" not in r for r in waves)
    # the per-launch facts of PR 33 ride in the record, and the
    # counter they fed reads what it read
    walked = [r["walk"] for r in waves]
    assert stats["kv_walk"]["waves"] == len(walked)
    assert stats["kv_walk"]["blocks_walked"] == sum(w[0] for w in walked)
    assert stats["kv_walk"]["blocks_tabled"] == sum(w[1] for w in walked)


def test_a_prefill_behind_waves_in_flight_says_how_many():
    inst = _engine(max_new_tokens=12, max_slots=4)
    _drive(inst, _prompts((5, 17, 9, 30, 12, 7)), step_s=0.001,
           gap_waves=3)
    records = inst.launch_records()
    prefills = [r for r in records if r["kind"] == "prefill"]
    waves = [r for r in records if r["kind"] == "decode"]
    behind = [r for r in prefills if r["ahead"] > 0]
    assert len(behind) >= 3 and any(r["ahead"] > 0 for r in waves)
    assert all(r["ahead"] <= 17 + len(prefills) for r in records)
    for r in behind:
        # queued, not fenced at once: a wave was dispatched before its
        # fence began, and the rows it was to join were decoding
        assert r["rows"] >= 1
        assert any(r["dispatch"][1] <= w["dispatch"][0] <= r["fence"][0]
                   for w in waves)
    # waves left in flight when their rows had all ended were made, so
    # they are recorded, unfenced
    given_up = [r for r in records if r["fence"] is None]
    assert all(r["kind"] == "decode" for r in given_up)
    assert [r["seq"] for r in records] == list(range(1, len(records) + 1))


# ------------------------------------------------- the ring and the sums

def _fake(seq, kind="decode", **facts):
    return dict({"seq": seq, "kind": kind, "program": "jit_pool_step",
                 "rows": 2, "ahead": 1, "dispatch": (1.0, 1.5),
                 "fence": (2.0, 3.0)}, **facts)


def test_the_ring_is_bounded_and_the_sums_are_not():
    tel = telemetry.EngineTelemetry("t_launch_ring")
    n = telemetry.LAUNCH_HISTORY + 904
    for seq in range(1, n + 1):
        tel.record_launch(_fake(seq))
    tel.record_launch(_fake(n + 1, "prefill", bucket=64, n_tail=50,
                            program="jit_paged_prefill_sample"))
    tel.record_launch(_fake(n + 2, "decode", fence=None))
    ring = tel.launch_records()
    assert len(ring) == telemetry.LAUNCH_HISTORY
    assert ring[0]["seq"] == n + 3 - telemetry.LAUNCH_HISTORY
    assert ring[-1]["seq"] == n + 2
    sums = tel.engine_stats()["launches"]
    assert sums["decode"] == {"count": n + 1, "rows": 2 * (n + 1),
                              "tail_tokens": 0, "ahead": n + 1,
                              "turnaround_s": 2.0 * n}
    assert sums["prefill"] == {
        "count": 1, "rows": 2, "tail_tokens": 50, "ahead": 1,
        "turnaround_s": 2.0, "by_bucket": {"64": [1, 50, 2.0]}}
    for _ in range(telemetry.LAUNCH_HISTORY + 5):
        tel.record_hold(0.002)
    tel.record_hold(0.5)
    hold = tel.engine_stats()["hold"]
    assert hold["count"] == telemetry.LAUNCH_HISTORY
    assert hold["p50"] == 2.0 and hold["max"] == 500.0


def test_an_engine_that_launched_nothing_reads_empty():
    tel = telemetry.EngineTelemetry("t_launch_empty")
    stats = tel.engine_stats()
    assert stats["launches"] == {} and stats["hold"]["count"] == 0
    assert tel.launch_records() == [] == telemetry.recent_launches()


@pytest.mark.parametrize("layout", ["paged", "paged_chunked"])
def test_the_sums_equal_the_ring_and_outlive_the_engine(layout):
    inst = _engine(**LAYOUTS[layout])
    _, stats, _ = _drive(inst, _prompts(), step_s=0.001, gap_waves=2)
    records = inst.launch_records()
    assert 0 < len(records) < telemetry.LAUNCH_HISTORY
    sums = stats["launches"]
    assert set(sums) == {r["kind"] for r in records}
    for kind, acc in sums.items():
        mine = [r for r in records if r["kind"] == kind]
        assert acc["count"] == len(mine)
        assert acc["rows"] == sum(r["rows"] for r in mine)
        assert acc["tail_tokens"] == sum(r.get("n_tail", 0) for r in mine)
        assert acc["ahead"] == sum(r["ahead"] for r in mine)
        assert acc["turnaround_s"] == pytest.approx(
            sum(r["fence"][1] - r["dispatch"][0] for r in mine
                if r["fence"]), abs=1e-5)
        buckets = {}
        for r in mine:
            if "bucket" in r:
                per = buckets.setdefault(str(r["bucket"]), [0, 0])
                per[0] += 1
                per[1] += r["n_tail"]
        assert {b: v[:2] for b, v in acc.get("by_bucket", {}).items()} \
            == buckets
    # one hold a yield
    assert stats["hold"]["count"] == stats["phases"]["yield"][0] > 0
    assert stats["hold"]["max"] * 1e-3 <= stats["phases"]["step"][1] + 1e-3
    # the engine is shut down; without it the process still answers
    del inst
    assert telemetry.recent_launches() == records


# ------------------------------------------------ on the profiler's clock

def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def test_spans_keep_their_names_and_carry_the_seq(tmp_path):
    """Under a profiler session the dispatch and fence spans arrive
    under their bare names (the reducers match names exactly), with the
    launch's fields as the event's stats."""
    from jax.profiler import ProfileData

    inst = _engine()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _drive(inst, _prompts((7, 19, 33)), step_s=0.001, gap_waves=2)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("raytpu.engine."):
                    spans.setdefault(e.name, []).append(_stats(e))
    records = {r["seq"]: r for r in inst.launch_records()}
    for leaf, kind in (("decode_dispatch", "decode"),
                       ("prefill_dispatch", "prefill")):
        got = spans["raytpu.engine." + leaf]
        assert sorted(s["seq"] for s in got) == sorted(
            seq for seq, r in records.items() if r["kind"] == kind)
        for s in got:
            r = records[s["seq"]]
            assert s["kind"] == kind and s["rows"] == r["rows"] \
                and s["ahead"] == r["ahead"]
            if kind == "prefill":
                assert (s["req"], s["bucket"], s["n_tail"]) == (
                    r["req"], r["bucket"], r["n_tail"])
    for leaf, kind in (("decode_fence", "decode"),
                       ("prefill_fence", "prefill")):
        assert sorted(s["seq"] for s in spans["raytpu.engine." + leaf]) \
            == sorted(seq for seq, r in records.items()
                      if r["kind"] == kind and r["fence"])
    steps = [s["n"] for s in spans["raytpu.engine.step"]]
    assert steps == list(range(1, len(steps) + 1))
    assert all(not s for s in spans["raytpu.engine.yield"])
    # no name grew a suffix
    assert all("#" not in name and "=" not in name for name in spans)
