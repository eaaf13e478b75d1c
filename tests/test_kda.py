"""ops/kda.py: the chunked delta rule and the one-token step against the
recurrence over time, in float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.kda import (_solve_unit_lower, kda_chunked, kda_recurrent,
                             kda_step)

B, H, DK, DV = 2, 3, 16, 8


def _inputs(seed, T, decay=(0.5, 0.999), beta_max=2.0, state=True):
    """Unit keys and queries, values N(0, 1), per-channel decays
    log-uniform in `decay`, beta uniform in (0, `beta_max`)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(k):
        x = jax.random.normal(k, (B, T, H, DK))
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    lo, hi = (float(np.log(d)) for d in decay)
    g = lo + (hi - lo) * jax.random.uniform(ks[3], (B, T, H, DK))
    s0 = jax.random.normal(ks[5], (B, H, DK, DV)) if state else None
    return (unit(ks[0]) * DK ** -0.5, unit(ks[1]),
            jax.random.normal(ks[2], (B, T, H, DV)), g,
            beta_max * jax.random.uniform(ks[4], (B, T, H))), s0


def _close(got, want, tol=2e-5):
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


@pytest.mark.parametrize("chunk,sub", [(16, 16), (64, 16), (32, 8)])
@pytest.mark.parametrize("T", [64, 83])
def test_chunked_is_the_recurrence(chunk, sub, T):
    """Chunks of 16 and 64, a length that is no multiple of the chunk,
    a carried-in state, beta up to 2."""
    xs, s0 = _inputs(T + chunk, T)
    o, s = kda_recurrent(*xs, s0)
    oc, sc, snap = kda_chunked(*xs, s0, chunk=chunk, sub=sub,
                               dtype=jnp.float32)
    assert snap is None and oc.shape == o.shape == (B, T, H, DV)
    _close(oc, o)
    _close(sc, s)


def test_two_calls_are_one():
    """The state a first call hands back carries a second: what a
    chunked admission does between its pieces."""
    xs, s0 = _inputs(7, 90)
    o, s = kda_chunked(*xs, s0, chunk=16, dtype=jnp.float32)[:2]
    cut = 37
    o1, s1, _ = kda_chunked(*(a[:, :cut] for a in xs), s0, chunk=16,
                            dtype=jnp.float32)
    o2, s2, _ = kda_chunked(*(a[:, cut:] for a in xs), s1, chunk=16,
                            dtype=jnp.float32)
    _close(jnp.concatenate([o1, o2], axis=1), o)
    _close(s2, s)


def test_no_state_is_a_zero_state():
    xs, _ = _inputs(3, 40, state=False)
    o, s = kda_recurrent(*xs)
    oc, sc, _ = kda_chunked(*xs, chunk=16, dtype=jnp.float32)
    _close(oc, o)
    _close(sc, s)


@pytest.mark.parametrize("capture", [0, 15, 16, 40, 82])
def test_the_captured_state_is_the_state_after_that_token(capture):
    xs, s0 = _inputs(11, 83)
    want = kda_recurrent(*(a[:, :capture + 1] for a in xs), s0)[1]
    o, s, snap = jax.jit(lambda c: kda_chunked(
        *xs, s0, chunk=16, sub=8, dtype=jnp.float32, capture=c))(capture)
    _close(snap, want)
    _close(s, kda_recurrent(*xs, s0)[1])


@pytest.mark.parametrize("chunk,sub", [(64, 16), (64, 64)])
def test_a_strong_decay_overflows_nothing(chunk, sub):
    """Decays down to 1e-4 a token: over a chunk of 64 the cumulative
    decay reaches exp(-590), and ``exp(-G_j)`` alone would be inf."""
    xs, s0 = _inputs(5, 128, decay=(1e-4, 0.9))
    o, s = kda_recurrent(*xs, s0)
    oc, sc, _ = kda_chunked(*xs, s0, chunk=chunk, sub=sub,
                            dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(oc))) and bool(
        jnp.all(jnp.isfinite(sc)))
    _close(oc, o)
    _close(sc, s)


def test_a_pad_is_an_identity_step():
    """beta = 0 and g = 0 at a position: its q, k and v move nothing,
    wherever the pads lie (a right-aligned tail's come first)."""
    (q, k, v, g, beta), s0 = _inputs(9, 48)
    real = jnp.arange(48) >= 13
    g_p = jnp.where(real[None, :, None, None], g, 0.0)
    b_p = jnp.where(real[None, :, None], beta, 0.0)
    o, s, _ = kda_chunked(q, k, v, g_p, b_p, s0, chunk=16,
                          dtype=jnp.float32)
    want_o, want_s = kda_recurrent(q[:, 13:], k[:, 13:], v[:, 13:],
                                   g[:, 13:], beta[:, 13:], s0)
    _close(o[:, 13:], want_o)
    _close(s, want_s)


def test_equal_keys_and_beta_two_stay_bounded():
    """The solve is forward substitution: with every key equal and beta
    2 the transition is a reflection, the powers of ``beta A`` reach
    2^k C(64, k), and the solution stays of magnitude 2."""
    T = 64
    k = jnp.broadcast_to(jnp.eye(DK)[0], (1, T, 1, DK))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, 1, DV))
    g = jnp.zeros((1, T, 1, DK))
    beta = jnp.full((1, T, 1), 2.0)
    o, s = kda_recurrent(k, k, v, g, beta)
    oc, sc, _ = kda_chunked(k, k, v, g, beta, chunk=64, dtype=jnp.float32)
    _close(oc, o)
    _close(sc, s)


def test_the_solve_is_the_inverse():
    low = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(1),
                                           (2, 3, 32, 32)), -1)
    rhs = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 32, 5))
    want = np.linalg.solve(np.eye(32) + np.asarray(low, np.float64),
                           np.asarray(rhs, np.float64))
    _close(_solve_unit_lower(low, rhs, 8), jnp.asarray(want, jnp.float32))


def test_a_step_is_one_step_of_the_recurrence():
    (q, k, v, g, beta), s0 = _inputs(4, 1)
    o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    want_o, want_s = kda_recurrent(q, k, v, g, beta, s0)
    _close(o, want_o[:, 0])
    _close(s, want_s)
    # by the definition, written out for one head of one row
    a = np.exp(np.asarray(g[0, 0, 0], np.float64))[:, None] \
        * np.asarray(s0[0, 0], np.float64)
    kk, vv = (np.asarray(x[0, 0, 0], np.float64) for x in (k, v))
    new = a + float(beta[0, 0, 0]) * np.outer(kk, vv - a.T @ kk)
    np.testing.assert_allclose(s[0, 0], new, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        o[0, 0], new.T @ np.asarray(q[0, 0, 0], np.float64), rtol=1e-5,
        atol=1e-5)


def test_an_idle_row_keeps_its_state_to_the_bit():
    """beta = 0 and g = 0 (a decode pool's row without a sequence)."""
    (q, k, v, _, _), s0 = _inputs(6, 1)
    _, s = kda_step(q[:, 0], k[:, 0], v[:, 0], jnp.zeros((B, H, DK)),
                    jnp.zeros((B, H)), s0)
    assert bool(jnp.all(s == s0))


def test_bf16_operands_accumulate_in_float32():
    """The serving dtype: the state handed back is float32 and within
    bf16's rounding of the recurrence."""
    xs, s0 = _inputs(8, 96, decay=(0.9, 0.999))
    o, s = kda_recurrent(*xs, s0)
    oc, sc, _ = kda_chunked(*xs, s0, chunk=64, dtype=jnp.bfloat16)
    assert oc.dtype == sc.dtype == jnp.float32
    _close(oc, o, tol=3e-2)
    _close(sc, s, tol=3e-2)
