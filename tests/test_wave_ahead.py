"""The continuous engine keeps decode waves in flight: the next wave
is queued behind them, reading the newest one's tokens on the device,
before the host fences and emits any (serve/llm.py `_wave`, `_chains`,
`_depth`, `_land`), and a prefill admitted meanwhile is queued among
them, its first token put into the wave behind it on the device
(`join_token`) and fenced in its turn.

What must not change is what callers get: every request, whenever it
arrives and whoever ended beside it, answers as it does alone on a
fresh engine."""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.serve.llm import build_llm_deployment  # noqa: E402

_F32 = {"dtype": jnp.float32, "use_flash": False, "remat": False}
_OVR = {"gpt2": _F32, "llama": _F32, "jamba": {}}
LENGTHS = (5, 17, 9, 30, 12, 7)


class _Fixed(list):
    """What the last waves took, held still."""

    def append(self, took):
        pass


def _build(family, **kw):
    kw.setdefault("max_new_tokens", 12)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("max_slots", 4)
    return build_llm_deployment(
        family, "nano", scheduler="continuous", kv_layout="paged",
        kv_block_size=16, prefill_bucket=16,
        config_overrides=_OVR[family], **kw)


def _prompts(seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 200, size=n).astype(np.int32)
            for n in LENGTHS]


def _run(dep, prompts, gap_waves=0, step_s=0.001):
    """Answers, engine stats and the log of waves: (a wave was in
    flight when this one was dispatched, a prefill's token joined it).
    Request i is sent once `gap_waves * i` waves have been dispatched
    (or nothing is left that would dispatch one), so arrivals fall
    between decode waves on any machine."""
    async def main():
        inst = dep.func_or_class()
        # every wave takes `step_s` as far as the engine can tell: with
        # a millisecond it runs ahead from the first wave on, however
        # long this machine's waves take
        inst._wave_s = _Fixed([step_s])
        waves = []
        wave = inst._wave

        def logged():
            waves.append((bool(inst._flight), len(inst._joins)))
            wave()

        inst._wave = logged
        busy = [0]

        async def one(i, p):
            while len(waves) < gap_waves * i and busy[0]:
                await asyncio.sleep(0)
            busy[0] += 1
            try:
                return await inst(p)
            finally:
                busy[0] -= 1

        try:
            outs = await asyncio.wait_for(asyncio.gather(
                *[one(i, p) for i, p in enumerate(prompts)]), 300)
            return outs, inst.engine_stats(), waves
        finally:
            inst.shutdown_engine()

    return asyncio.run(main())


@pytest.mark.parametrize("family", ["gpt2", "llama", "jamba"])
def test_answers_with_a_wave_in_flight_equal_the_solo_answers(family):
    dep = _build(family)
    prompts = _prompts()
    outs, stats, waves = _run(dep, prompts, gap_waves=3)
    for p, out in zip(prompts, outs):
        (solo,), _, _ = _run(dep, [p])
        np.testing.assert_array_equal(out, solo)
    # the paths under test were taken: waves queued behind the one in
    # flight, and prefills whose first token joined on the device
    assert sum(chained for chained, _ in waves) > len(waves) // 2
    assert sum(joined for _, joined in waves) >= 3
    # every wave dispatched was fenced, but for those left in flight
    # whose rows had all ended
    ph = stats["phases"]
    assert 0 <= ph["decode_dispatch"][0] - ph["decode_fence"][0] <= 16


def test_a_wave_longer_than_the_budget_is_fenced_before_the_next():
    """Where one wave takes longer than `_AHEAD_S`, nothing is queued
    behind a wave: every wave and every prefill is fenced before the
    next program is dispatched, as before the engine ran ahead."""
    dep = _build("gpt2")
    prompts = _prompts(3)
    outs, stats, waves = _run(dep, prompts, gap_waves=3, step_s=1.0)
    assert not any(chained or joins for chained, joins in waves)
    for p, out in zip(prompts, outs):
        (solo,), _, _ = _run(dep, [p])
        np.testing.assert_array_equal(out, solo)


def test_no_wave_is_dispatched_for_rows_that_all_end_by_count():
    """Four requests in lockstep end in the same wave: the engine
    dispatches max_new_tokens - 1 waves, none for nothing."""
    dep = _build("gpt2", max_new_tokens=6)
    prompts = _prompts()[:4]
    outs, stats, waves = _run(dep, prompts)
    assert len(waves) == 5 and not any(j for _, j in waves)
    assert stats["phases"]["decode_fence"][0] == 5
    assert all(len(o) == len(p) + 6 for o, p in zip(outs, prompts))


def test_a_row_ended_by_a_stop_token_is_dropped_from_the_wave_behind():
    """A stop token is seen only when the wave lands, with the next
    wave already queued: the row is stepped once more and that token
    is dropped; its neighbours and its slot's next tenant answer as
    they do alone."""
    prompts = _prompts(1)
    (solo,), _, _ = _run(_build("gpt2"), [prompts[0]])
    stop = int(solo[len(prompts[0]) + 4])      # its fifth new token
    dep = _build("gpt2", max_slots=2, eos_id=stop)
    outs, _, waves = _run(dep, prompts, gap_waves=2)
    assert any(chained for chained, _ in waves)
    ended_early = 0
    for p, out in zip(prompts, outs):
        (alone,), _, _ = _run(dep, [p])
        np.testing.assert_array_equal(out, alone)
        new = out[len(p):]
        if len(new) < 12:
            ended_early += 1
            assert new[-1] == stop and stop not in new[:-1]
    assert ended_early >= 1


def test_sampled_waves_draw_the_keys_they_drew_unchained():
    """With a temperature the wave's key is split from the engine's
    stream at dispatch, chained or not: concurrent requests sent at
    once answer the same on every run, and the stream advances once a
    wave and once an admission."""
    dep = _build("gpt2", temperature=0.8)
    prompts = _prompts(2)[:4]
    a, _, waves = _run(dep, prompts)
    b, _, _ = _run(dep, prompts)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert len(waves) == 11 and all(c for c, _ in waves[1:])


@pytest.mark.parametrize("step_s, streaming, depth", [
    (None, False, 0),         # no wave timed yet
    (0.0127, False, 10),      # 0.13 s of 12.7 ms waves
    (0.163, False, 0),        # one wave is more than that: none queued
    (0.0005, False, 16),      # the cap
    (0.0127, True, 0),        # a chunked prompt is streaming in
])
def test_depth_is_a_time_on_the_chip_not_a_count(step_s, streaming,
                                                  depth):
    inst = _build("gpt2").func_or_class()
    try:
        if step_s is not None:
            # the median of what the last waves took: one wave that
            # waited for a prefill does not move it
            inst._wave_s.extend([step_s] * 9 + [step_s + 0.04])
        if streaming:
            inst._slots[0] = {"state": "prefill"}
        assert inst._depth() == depth
    finally:
        inst._slots[0] = None
        inst.shutdown_engine()
