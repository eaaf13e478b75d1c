"""Host phases on the profiler's clock (``_private/telemetry.Phases``)
and their use in the serving engine's loop: the leaf phases of every
``raytpu.engine.step`` partition it exactly, a ``jax.profiler`` trace
of the run holds them as host spans, ``engine_stats()["phases"]``
reports the table, and a phase costs microseconds when no trace is
being taken."""

import asyncio
import glob

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu._private import scopes  # noqa: E402
from ray_tpu._private.telemetry import Phases  # noqa: E402
from ray_tpu.serve.llm import build_llm_deployment  # noqa: E402

_OVR = {"dtype": jnp.float32, "use_flash": False, "remat": False}


def _prompts(n, lo=6, hi=20, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(2, 50, size=rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _engine(**kw):
    args = dict(scheduler="continuous", kv_layout="paged",
                kv_block_size=16, prefill_bucket=16, max_slots=2,
                max_new_tokens=6, temperature=0.0, config_overrides=_OVR)
    args.update(kw)
    return build_llm_deployment("gpt2", "nano", **args).func_or_class()


def _drive(inst, prompts):
    async def main():
        try:
            return await asyncio.gather(*[inst(p) for p in prompts])
        finally:
            inst.shutdown_engine()

    return asyncio.run(main())


# ------------------------------------------------------------ primitive

def _leaves_and_step_ns(ph):
    """(sum of the leaves' whole nanoseconds, the steps' nanoseconds):
    the table behind ``snapshot()``, before it is turned to seconds."""
    return (sum(ns for name, (_, ns) in ph._table.items()
                if name != scopes.STEP), ph._table[scopes.STEP][1])


def test_leaves_partition_a_step_exactly():
    ph = Phases("t")
    for n_steps in (1, 2, 3):             # after every step, not on average
        with ph.step():
            with ph.phase("a"):
                with ph.phase("a.inner"):
                    sum(range(100))
                sum(range(100))
            sum(range(100))               # between phases: "loop"
            with ph.phase("b") as b:
                pass
        leaves, steps = _leaves_and_step_ns(ph)
        assert leaves == steps            # whole nanoseconds
        assert ph._table[scopes.STEP][0] == n_steps
    table = ph.snapshot()
    assert set(table) == {"step", "loop", "a", "a.inner", "b"}
    assert table["step"][0] == 3 and table["a"][0] == 3
    assert all(v[1] >= 0.0 for v in table.values())
    assert sum(v[1] for k, v in table.items() if k != "step") \
        == pytest.approx(table["step"][1], rel=1e-12)
    assert 0.0 < b.t0 <= b.t1                    # perf_counter stamps


def test_a_phase_closes_when_its_body_raises():
    ph = Phases("t")
    with pytest.raises(RuntimeError):
        with ph.step():
            with ph.phase("a"):
                raise RuntimeError("boom")
    leaves, steps = _leaves_and_step_ns(ph)
    assert leaves == steps
    with ph.step():                       # and the next step is whole
        with ph.phase("a"):
            pass
    leaves, steps = _leaves_and_step_ns(ph)
    assert leaves == steps and ph.snapshot()["a"][0] == 2


def test_a_phase_is_a_host_span_only_while_a_trace_is_taken(tmp_path):
    assert scopes.span_name("engine", "admit") == "raytpu.engine.admit"
    ph = Phases("t")
    with ph.phase("before"):              # no session: records nothing
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with ph.phase("during"):
            pass
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events}
    assert "raytpu.t.during" in names and "raytpu.t.before" not in names
    assert ph.snapshot()["before"][0] == ph.snapshot()["during"][0] == 1


@pytest.mark.parametrize("attrs", [
    {}, {"seq": 7, "kind": "decode", "rows": 32, "ahead": 3}],
    ids=["bare", "with_attributes"])
def test_phase_costs_microseconds_without_a_trace(per_call_us, attrs):
    """The budget per ``phase()`` call when the instrumentation is off
    (no profiler session): measured in isolation, min of repeats.  An
    engine step opens about a dozen, so 15 us each is under 0.2 ms a
    step; the measured cost is 1-2 us, with a launch's attributes
    passed as without (they are encoded only under a session)."""
    ph = Phases("budget")

    def one():
        with ph.phase("emit", **attrs):
            pass

    us = per_call_us(one)
    assert us < 15.0, f"phase() costs {us:.2f} us a call"
    assert ph.snapshot()["emit"][0] == per_call_us.calls


# --------------------------------------------------------------- engine

def test_engine_steps_are_partitioned_by_their_leaves(tmp_path):
    inst = _engine()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        outs = _drive(inst, _prompts(5))
    finally:
        jax.profiler.stop_trace()
    assert all(len(o) for o in outs)
    leaves, steps_ns = _leaves_and_step_ns(inst._phases)
    assert leaves == steps_ns             # whole nanoseconds, every step
    stats = inst.engine_stats()["phases"]
    n_steps = stats["step"][0]
    assert n_steps >= 6
    assert {"admit", "kv.reserve", "prefill_dispatch", "prefill_fence",
            "rng_split", "decode_dispatch", "decode_fence", "emit",
            "hooks", "yield", "loop", "step"} <= set(stats) \
        <= set(scopes.ENGINE_PHASES) | {scopes.STEP}
    waves = inst.engine_stats()["engine_steps"]
    assert stats["decode_fence"][0] == waves == stats["emit"][0]

    # and the profiler's host plane holds the spans, on its own clock
    from jax.profiler import ProfileData

    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for e in line.events
             if e.name.startswith(scopes.SPAN_PREFIX)]
    counts = {n: names.count(n) for n in set(names)}
    assert counts["raytpu.engine.step"] >= n_steps - 1
    assert counts["raytpu.engine.decode_fence"] == waves
    assert counts["raytpu.engine.prefill_fence"] == 5
    assert "raytpu.engine.loop" not in counts     # booked, not traced
    assert set(counts) <= {scopes.span_name(scopes.ENGINE, p)
                           for p in scopes.ENGINE_PHASES + (scopes.STEP,)}


def test_record_step_reads_the_phases_stamps():
    """``record_step`` takes its duration from the phases' stamps, not
    from a clock pair of its own: from the wave's dispatch to its
    fence's end, or, for a wave that was queued behind the one in
    flight, from that one's fence (serve/llm.py ``_land_wave``).  So the
    steps' durations hold every dispatch and fence, and they tile the
    loop's time: a wave in flight across two iterations is counted
    once, and the sum stays inside the steps' wall."""
    inst = _engine()
    _drive(inst, _prompts(3, seed=1))
    table = inst._phases.snapshot()
    steps = [d for _, d, _ in inst._telemetry._steps]
    phases = table["decode_dispatch"][1] + table["decode_fence"][1]
    assert all(d > 0 for d in steps)
    assert phases <= sum(steps) \
        <= table[scopes.STEP][1] + 50e-6 * len(steps)


def test_spec_round_is_one_leaf():
    from ray_tpu.serve.llm import SpecConfig

    inst = _engine(spec_decode=SpecConfig(k=2))
    _drive(inst, _prompts(2, seed=2))
    leaves, steps_ns = _leaves_and_step_ns(inst._phases)
    assert leaves == steps_ns
    assert inst._phases.snapshot()["spec_round"][0] >= 1
