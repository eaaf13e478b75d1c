"""Throughput over whole steps, percentiles, due-time arithmetic."""

import numpy as np
import pytest

from benchmark import estimators as E


def _fences(step_s, n, slow_at=None, slow_s=0.0, t0=100.0):
    t, out = t0, [t0]
    for i in range(n):
        t += step_s + (slow_s if i == slow_at else 0.0)
        out.append(t)
    return out


def test_whole_steps_between_fences():
    f = _fences(0.25, 40)
    rate, n, elapsed = E.whole_step_rate(f, 24 * 1024)
    assert n == 40 and elapsed == pytest.approx(10.0)
    assert rate == pytest.approx(24 * 1024 / 0.25)


@pytest.mark.parametrize("window", [9.9, 10.0, 10.1, 10.24])
def test_the_cut_of_the_window_moves_nothing(window):
    """A clock window counts 39 or 40 steps depending on where it
    falls; whole steps between fences read the same rate wherever the
    dispatch stopped."""
    f = _fences(0.25, 60)
    upto = next(i for i in range(1, len(f))
                if E.should_stop(f[:i + 1], window))
    seen = f[:upto + 2]            # the step in flight is drained too
    rate, n, _ = E.whole_step_rate(seen, 1000.0)
    assert rate == pytest.approx(4000.0)
    assert n == len(seen) - 1


def test_a_slow_step_is_part_of_the_rate_and_is_named():
    f = _fences(0.25, 40, slow_at=17, slow_s=0.5)
    rate, n, elapsed = E.whole_step_rate(f, 1000.0)
    assert elapsed == pytest.approx(10.5)
    assert rate == pytest.approx(40 * 1000.0 / 10.5)
    s = E.step_time_summary(f)
    assert s["slowest_step"] == 17
    assert s["max_ms"] == pytest.approx(750.0)
    assert s["p50_ms"] == pytest.approx(250.0)
    assert s["min_ms"] == pytest.approx(250.0)


def test_whole_step_rate_refuses_nonsense():
    with pytest.raises(ValueError):
        E.whole_step_rate([1.0], 1.0)
    with pytest.raises(ValueError):
        E.whole_step_rate([2.0, 2.0], 1.0)


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
@pytest.mark.parametrize("n", [1, 2, 7, 100])
def test_percentile_is_numpys(q, n):
    xs = np.random.default_rng(n).normal(size=n).tolist()
    assert E.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_ttft_counts_from_the_due_time():
    # due at 10.0, the generator got round to it at 10.3, first token
    # at 10.8: the user waited 800 ms, not 500
    assert E.ttft_ms(10.8, 10.0) == pytest.approx(800.0)
    assert E.token_gaps_ms([1.0, 1.2, 1.5]) == pytest.approx(
        [200.0, 300.0])
    assert E.token_gaps_ms([1.0]) == []


def _waves(step, n, per_wave, t0=0.0):
    return [t0 + step * (i + 1) for i in range(n) for _ in range(per_wave)]


@pytest.mark.parametrize("t_end,waves", [(10.01, 45), (10.1, 45),
                                         (10.19, 45), (10.21, 46)])
def test_emission_rate_is_tokens_in_the_window_over_its_length(t_end,
                                                               waves):
    """Both ends are the clock's: a wave counts if its stamp is inside,
    whole or not at all."""
    stamps = _waves(0.2, 100, 32)
    rate, n, span = E.emission_rate(stamps, 1.0, t_end)
    assert span == pytest.approx(t_end - 1.0)
    assert n == 32 * waves
    assert rate == pytest.approx(32 * waves / (t_end - 1.0))


def test_emission_rate_opens_at_the_offer_not_at_the_first_token():
    # load offered at 0.0, first wave only at 3.0 (a slow start)
    stamps = _waves(0.2, 50, 4, t0=2.8)
    rate, n, span = E.emission_rate(stamps, 0.0, 10.0)
    assert span == pytest.approx(10.0) and n == 4 * 36
    assert rate == pytest.approx(14.4)


def test_emission_rate_counts_a_stall_in_the_middle():
    stamps = _waves(0.2, 10, 4) + _waves(0.2, 40, 4, t0=4.0)
    rate, n, span = E.emission_rate(stamps, 0.0, 10.0)
    assert span == pytest.approx(10.0) and n == 40 + 4 * 30
    assert rate == pytest.approx(16.0)      # 20.0 without the stall


@pytest.mark.parametrize("waves_before_silence,want", [
    (50, 20.0), (40, 16.0), (29, 11.6)])
def test_silence_at_the_tail_lowers_the_rate(waves_before_silence, want):
    """Tokens stop before the window's end, whether or not they ever
    come back: the silence is inside the window.  (The first estimator
    closed its span on the last token inside the window and read 20.0
    in every case: an engine that hung 5.8 s into a 10 s window kept
    its full rate.)"""
    stamps = _waves(0.2, waves_before_silence, 4)
    for resumed in ([], [10.1] * 4):
        rate, n, span = E.emission_rate(stamps + resumed, 0.0, 10.0)
        assert span == pytest.approx(10.0)
        assert rate == pytest.approx(want)


def test_no_token_in_the_window_is_nothing_to_read():
    assert E.emission_rate([1.0], 1.0, 2.0) is None     # at the open
    assert E.emission_rate([2.5], 0.0, 2.0) is None     # after the cut
    assert E.emission_rate([], 0.0, 2.0) is None
