"""ops/kda.py: the chunked delta rule and the one-token step against the
recurrence over time, in float32 on the CPU; each form twice: the `jnp`
one at small heads, and the kernel (`kda_chunk`, `kda_decode`, in the
Pallas interpreter) at heads of whole lanes; and both again with ONE
decay a head (``g`` of trailing size 1), where the `jnp` chunk is
matmuls only and the kernels (``delta_chunk``, ``delta_decode``) take
heads of 96 x 192."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda
from ray_tpu.ops.kda import (_solve_unit_lower, kda_chunk, kda_chunked,
                             kda_decode, kda_prefill, kda_recurrent,
                             kda_step)

B, H, DK, DV = 2, 3, 16, 8
#: the oracle and the `jnp` chunk form as programs the module's cases
#: share: a scan run an operation at a time is compiled anew a call
_recurrent = jax.jit(kda_recurrent)
_chunked = jax.jit(kda_chunked, static_argnames=("chunk", "sub", "dtype"))
#: the two chunk forms and the sizes each is run at: the kernel takes
#: heads of whole lanes, one row of three heads (an odd count: a head a
#: grid step)
FORMS = {"jnp": (_chunked, (B, H, DK, DV)),
         "kernel": (functools.partial(kda_chunk, interpret=True),
                    (1, 3, 128, 128))}
forms = pytest.mark.parametrize("form", sorted(FORMS))


def _kernel_step(q, k, v, g, beta, state):
    """`kda_step`'s contract through the kernel: a stack of one."""
    o, stack = kda_decode(q, k, v, g, beta, state[None], 0, interpret=True)
    return o, stack[0]


#: the two step forms and the sizes each is run at: the kernel takes
#: heads of whole lanes in groups of eight
STEP_FORMS = {"jnp": (kda_step, (B, H, DK, DV)),
              "kernel": (_kernel_step, (2, 8, 128, 128))}
step_forms = pytest.mark.parametrize("form", sorted(STEP_FORMS))


def _inputs(seed, T, decay=(0.5, 0.999), beta_max=2.0, state=True,
            dims=(B, H, DK, DV)):
    """Unit keys and queries, values N(0, 1), per-channel decays
    log-uniform in `decay`, beta uniform in (0, `beta_max`)."""
    B, H, DK, DV = dims
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


def _traced_grad(loss, x):
    """`loss`'s gradient at `x` traced ONCE: (the jaxpr's text, the
    gradient)."""
    traced = jax.jit(jax.grad(loss)).trace(x)
    return str(traced.jaxpr), traced.lower().compile()(x)


@pytest.mark.parametrize("form,chunk,sub,T", [
    ("jnp", c, s, T) for c, s in [(16, 16), (64, 16), (32, 8)]
    for T in (64, 83)] + [
    # one chunk; no multiple of the chunk; chunks that fill a grid step
    # (four) and one more, which is no multiple of the chunks a step
    ("kernel", 64, 16, 64), ("kernel", 64, 16, 83),
    ("kernel", 32, 16, 128), ("kernel", 32, 16, 160),
    ("kernel", 16, 8, 50)])
def test_chunked_is_the_recurrence(form, chunk, sub, T):
    """Chunks of 16 and 64, a length that is no multiple of the chunk,
    a carried-in state, beta up to 2."""
    chunked, dims = FORMS[form]
    xs, s0 = _inputs(T + chunk, T, dims=dims)
    o, s = _recurrent(*xs, s0)
    oc, sc, snap = chunked(*xs, s0, chunk=chunk, sub=sub,
                           dtype=jnp.float32)
    assert snap is None and oc.shape == o.shape == (
        dims[0], T, dims[1], dims[3])
    _close(oc, o)
    _close(sc, s)
    if form == "kernel":        # and the `jnp` form it stands for
        oj, sj, _ = _chunked(*xs, s0, chunk=chunk, sub=sub,
                                dtype=jnp.float32)
        _close(oc, oj)
        _close(sc, sj)


@forms
def test_two_calls_are_one(form):
    """The state a first call hands back carries a second: what a
    chunked admission does between its pieces."""
    chunked, dims = FORMS[form]
    xs, s0 = _inputs(7, 90, dims=dims)
    o, s = chunked(*xs, s0, chunk=16, dtype=jnp.float32)[:2]
    cut = 37
    o1, s1, _ = chunked(*(a[:, :cut] for a in xs), s0, chunk=16,
                        dtype=jnp.float32)
    o2, s2, _ = chunked(*(a[:, cut:] for a in xs), s1, chunk=16,
                        dtype=jnp.float32)
    _close(jnp.concatenate([o1, o2], axis=1), o)
    _close(s2, s)


@forms
def test_no_state_is_a_zero_state(form):
    chunked, dims = FORMS[form]
    xs, _ = _inputs(3, 40, state=False, dims=dims)
    o, s = _recurrent(*xs)
    oc, sc, _ = chunked(*xs, chunk=16, dtype=jnp.float32)
    _close(oc, o)
    _close(sc, s)


@pytest.mark.parametrize("form,capture", [
    ("jnp", c) for c in (0, 15, 16, 40, 82)] + [
    # a chunk's first, a middle and its last column; the first chunk's
    # and the last's, which the length does not fill
    ("kernel", c) for c in (0, 16, 23, 31, 82)])
def test_the_captured_state_is_the_state_after_that_token(form, capture):
    chunked, dims = FORMS[form]
    xs, s0 = _inputs(11, 83, dims=dims)
    want = _recurrent(*(a[:, :capture + 1] for a in xs), s0)[1]
    o, s, snap = _captured(form)(xs, s0, capture)
    _close(snap, want)
    _close(s, _recurrent(*xs, s0)[1])
    _close(o, _recurrent(*xs, s0)[0])


@functools.lru_cache(maxsize=None)
def _captured(form):
    """One compiled program a form: the column is traced."""
    return jax.jit(lambda xs, s0, c: FORMS[form][0](
        *xs, s0, chunk=16, sub=8, dtype=jnp.float32, capture=c))


@pytest.mark.parametrize("form,chunk,sub", [
    ("jnp", 64, 16), ("jnp", 64, 64), ("kernel", 64, 16)])
def test_a_strong_decay_overflows_nothing(form, chunk, sub):
    """Decays down to 1e-4 a token: over a chunk of 64 the cumulative
    decay reaches exp(-590), and ``exp(-G_j)`` alone would be inf."""
    chunked, dims = FORMS[form]
    xs, s0 = _inputs(5, 128, decay=(1e-4, 0.9), dims=dims)
    o, s = _recurrent(*xs, s0)
    oc, sc, _ = chunked(*xs, s0, chunk=chunk, sub=sub, dtype=jnp.float32)
    assert bool(jnp.all(jnp.isfinite(oc))) and bool(
        jnp.all(jnp.isfinite(sc)))
    _close(oc, o)
    _close(sc, s)


@pytest.mark.parametrize("form,pads", [("jnp", 13), ("kernel", 13),
                                       ("kernel", 35)])
def test_a_pad_is_an_identity_step(form, pads):
    """beta = 0 and g = 0 at a position: its q, k and v move nothing,
    wherever the pads lie (a right-aligned tail's come first: 13 of
    them inside the first chunk, 35 the first two chunks whole and
    more)."""
    chunked, dims = FORMS[form]
    (q, k, v, g, beta), s0 = _inputs(9, 48, dims=dims)
    real = jnp.arange(48) >= pads
    g_p = jnp.where(real[None, :, None, None], g, 0.0)
    b_p = jnp.where(real[None, :, None], beta, 0.0)
    o, s, _ = chunked(q, k, v, g_p, b_p, s0, chunk=16, dtype=jnp.float32)
    want_o, want_s = _recurrent(q[:, pads:], k[:, pads:], v[:, pads:],
                                   g[:, pads:], beta[:, pads:], s0)
    _close(o[:, pads:], want_o)
    _close(s, want_s)


@forms
def test_chunks_of_pads_leave_the_state_to_the_bit(form):
    """A bucket's left edge: whole chunks of pads in front change no
    bit of the state handed in."""
    chunked, dims = FORMS[form]
    (q, k, v, g, beta), s0 = _inputs(9, 48, dims=dims)
    s = chunked(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), s0,
                chunk=16, dtype=jnp.float32)[1]
    assert bool(jnp.all(s == s0))


@forms
def test_equal_keys_and_beta_two_stay_bounded(form):
    """The solve is forward substitution: with every key equal and beta
    2 the transition is a reflection, the powers of ``beta A`` reach
    2^k C(64, k), and the solution stays of magnitude 2."""
    T = 64
    chunked, (_, _, DK, DV) = FORMS[form]
    k = jnp.broadcast_to(jnp.eye(DK)[0], (1, T, 1, DK))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, 1, DV))
    g = jnp.zeros((1, T, 1, DK))
    beta = jnp.full((1, T, 1), 2.0)
    o, s = _recurrent(k, k, v, g, beta)
    oc, sc, _ = chunked(k, k, v, g, beta, chunk=64, dtype=jnp.float32)
    _close(oc, o)
    _close(sc, s)


def test_the_solve_is_the_inverse():
    low = 0.3 * jnp.tril(jax.random.normal(jax.random.PRNGKey(1),
                                           (2, 3, 32, 32)), -1)
    rhs = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 32, 5))
    want = np.linalg.solve(np.eye(32) + np.asarray(low, np.float64),
                           np.asarray(rhs, np.float64))
    _close(_solve_unit_lower(low, rhs, 8), jnp.asarray(want, jnp.float32))


@step_forms
def test_a_step_is_one_step_of_the_recurrence(form):
    step, dims = STEP_FORMS[form]
    (q, k, v, g, beta), s0 = _inputs(4, 1, dims=dims)
    o, s = step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    want_o, want_s = _recurrent(q, k, v, g, beta, s0)
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


@step_forms
def test_an_idle_row_keeps_its_state_to_the_bit(form):
    """beta = 0 and g = 0 (a decode pool's row without a sequence),
    beside a row that moves."""
    step, dims = STEP_FORMS[form]
    (q, k, v, g, beta), s0 = _inputs(6, 1, dims=dims)
    idle = jnp.arange(dims[0]) == 0
    _, s = step(q[:, 0], k[:, 0], v[:, 0],
                jnp.where(idle[:, None, None], 0.0, g[:, 0]),
                jnp.where(idle[:, None], 0.0, beta[:, 0]), s0)
    assert bool(jnp.all(s[0] == s0[0]))
    assert not bool(jnp.all(s[1] == s0[1]))


@step_forms
def test_steps_of_equal_keys_and_beta_two_stay_bounded(form):
    """With every key equal and beta 2 a step is a reflection (an
    eigenvalue of -1): twelve of them stay where the recurrence is."""
    T = 12
    step, (_, heads, DK, DV) = STEP_FORMS[form]
    k = jnp.broadcast_to(jnp.eye(DK)[0], (1, T, heads, DK))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, heads, DV))
    g = jnp.zeros((1, T, heads, DK))
    beta = jnp.full((1, T, heads), 2.0)
    want_o, want_s = _recurrent(k, k, v, g, beta)
    s = jnp.zeros((1, heads, DK, DV))
    for t in range(T):
        o, s = step(k[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], s)
        _close(o, want_o[:, t])
    _close(s, want_s)
    assert float(jnp.max(jnp.abs(s))) <= 2.0 * float(
        jnp.max(jnp.sum(jnp.abs(v), axis=1)))


@pytest.mark.parametrize("j", range(3))
@step_forms
def test_a_step_on_a_stack_moves_the_layer_it_names(form, j):
    """`kda_decode` on the KDA layers' stack: layer `j` is `kda_step` on
    its slice, the two layers not named come back to the bit."""
    dims = STEP_FORMS[form][1]
    (q, k, v, g, beta), _ = _inputs(10 + j, 1, dims=dims)
    stack = jax.random.normal(jax.random.PRNGKey(3), (3, *dims))
    o, after = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                          stack, j, interpret=form == "kernel")
    want_o, want = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            stack[j])
    _close(o, want_o)
    _close(after[j], want)
    for other in set(range(3)) - {j}:
        assert bool(jnp.all(after[other] == stack[other]))


def test_the_step_kernel_runs_only_where_it_fits(monkeypatch):
    """`kda_decode` picks by what it can see: off the chip, at heads
    that are no whole lanes or in no whole groups of eight it is
    `kda_step` on the layer; a differentiated kernel form is that too,
    forward and backward."""
    def program(dims, **kw):
        (q, k, v, g, beta), s0 = _inputs(2, 1, dims=dims)
        return str(jax.make_jaxpr(lambda stack: kda_decode(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], stack, 1,
            **kw))(jnp.stack([s0, s0])))

    wide = (1, 8, 128, 128)
    assert "pallas_call" not in program(wide)              # the CPU
    assert "pallas_call" in program(wide, interpret=True)
    assert "pallas_call" not in program((B, H, DK, DV), interpret=True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "pallas_call" in program(wide)
    assert "pallas_call" not in program((B, H, DK, DV))     # heads of 16 x 8
    assert "pallas_call" not in program((1, 4, 128, 128))   # half a group
    monkeypatch.undo()

    (q, k, v, g, beta), s0 = _inputs(2, 1, dims=wide)

    def loss(interpret, v):
        o, stack = kda_decode(q[:, 0], k[:, 0], v, g[:, 0], beta[:, 0],
                              s0[None], 0, interpret=interpret)
        return jnp.sum(o * o) + jnp.sum(stack)

    text, grad = _traced_grad(functools.partial(loss, True), v[:, 0])
    _close(grad, _traced_grad(functools.partial(loss, False), v[:, 0])[1])
    assert "pallas_call" not in text


@forms
def test_bf16_operands_accumulate_in_float32(form):
    """The serving dtype: the state handed back is float32 and within
    bf16's rounding of the recurrence."""
    chunked, dims = FORMS[form]
    xs, s0 = _inputs(8, 96, decay=(0.9, 0.999), dims=dims)
    o, s = _recurrent(*xs, s0)
    oc, sc, _ = chunked(*xs, s0, chunk=64, dtype=jnp.bfloat16)
    assert oc.dtype == sc.dtype == jnp.float32
    _close(oc, o, tol=3e-2)
    _close(sc, s, tol=3e-2)


def test_heads_as_lane_slices_are_heads_leading():
    """The kernel reads a head as a lane slice of the folded row, two
    heads a grid step: what each head alone gives (a row of one head is
    its own folded row), the snapshot too."""
    dims = (1, 4, 128, 128)
    xs, s0 = _inputs(12, 40, dims=dims)
    kernel = functools.partial(kda_chunk, chunk=16, sub=8,
                               dtype=jnp.float32, capture=jnp.int32(21),
                               interpret=True)
    together = kernel(*xs, s0)
    for h in range(dims[1]):
        alone = kernel(*(a[:, :, h:h + 1] for a in xs), s0[:, h:h + 1])
        _close(together[0][:, :, h:h + 1], alone[0])
        _close(together[1][:, h:h + 1], alone[1])
        _close(together[2][:, h:h + 1], alone[2])


def test_the_kernel_runs_only_where_it_fits(monkeypatch):
    """`kda_prefill` picks by what it can see: off the chip, at heads
    that are no whole lanes or at one column it is the `jnp` form; a
    differentiated kernel form is the `jnp` form too, forward and
    backward."""
    called = []
    monkeypatch.setattr(kda, "kda_chunk", lambda *a, **k: called.append(
        "kernel") or kda_chunk(*a, interpret=True, **k))
    xs, s0 = _inputs(2, 24)
    want = _chunked(*xs, s0, chunk=16, dtype=jnp.float32)
    got = kda_prefill(*xs, s0, chunk=16, dtype=jnp.float32)
    assert not called                               # the CPU
    _close(got[0], want[0])
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    kda_prefill(*xs, s0, chunk=16, dtype=jnp.float32)
    assert not called                               # heads of 16 x 8
    wide, w0 = _inputs(2, 24, dims=(1, 1, 128, 128))
    kda_prefill(*(a[:, :1] for a in wide), w0, chunk=16, dtype=jnp.float32)
    assert not called                               # one column
    got = kda_prefill(*wide, w0, chunk=16, dtype=jnp.float32)
    assert called == ["kernel"]
    _close(got[0], _chunked(*wide, w0, chunk=16, dtype=jnp.float32)[0])

    def loss(form, v):
        q, k, _, g, beta = wide
        o, s, _ = form(q, k, v, g, beta, w0, chunk=16, dtype=jnp.float32)
        return jnp.sum(o * o) + jnp.sum(s)

    text, grad = _traced_grad(functools.partial(loss, functools.partial(
        kda_chunk, interpret=True)), wide[2])
    _close(grad, _traced_grad(functools.partial(loss, kda_chunked),
                              wide[2])[1])
    assert "pallas_call" not in text and "scan" in text


# -- one decay a head ---------------------------------------------------------

#: keys of 12 by values of 24 (not square, no whole lanes) and five
#: heads (no multiple of 8): the shape of a model whose heads are 96 by
#: 192 and thirty
ONE = (1, 5, 12, 24)
#: ... and those ratios at the smallest sizes the kernel for one decay
#: a head takes: keys three quarters of a tile of lanes, values twice
#: the keys, five heads side by side
GATE = (1, 5, 96, 192)
#: the two forms of one decay a head and the sizes each is run at
ONE_FORMS = {"jnp": ONE, "kernel": GATE}
one_forms = pytest.mark.parametrize("form", sorted(ONE_FORMS))


def _one_decay(seed, T, dims=ONE, **kw):
    """`_inputs` with a decay a HEAD: g (B, T, H, 1)."""
    (q, k, v, g, beta), s0 = _inputs(seed, T, dims=dims, **kw)
    return (q, k, v, g[..., :1], beta), s0


@functools.lru_cache(maxsize=None)
def _compiled(chunk, sub=16, dtype=jnp.float32, form="jnp"):
    """A chunk form as one program: what `kda_chunked` takes for all
    chunks at once lies outside the scan, and would run an operation at
    a time; the kernel's is `kda_chunk` in the Pallas interpreter."""
    if form == "kernel":
        return functools.partial(kda_chunk, chunk=chunk, sub=sub,
                                 dtype=dtype, interpret=True)
    return functools.partial(_chunked, chunk=chunk, sub=sub, dtype=dtype)


@pytest.mark.parametrize("form,dims,chunk,sub,T", [
    ("jnp", dims, *c) for dims in (ONE, (B, H, DK, DV))
    for c in ((16, 16, 64), (64, 16, 83), (32, 8, 83))] + [
    # one chunk and a tail; chunks that fill grid steps and one more;
    # sub-chunks of one tile
    ("kernel", GATE, 64, 16, 83), ("kernel", GATE, 32, 16, 160),
    ("kernel", GATE, 16, 8, 50)],
    ids=lambda p: "x".join(map(str, p)) if isinstance(p, tuple) else None)
def test_one_decay_a_head_chunked_is_the_recurrence(form, dims, chunk, sub,
                                                    T):
    """The matmul form: a carried-in state, beta up to 2, a length that
    is no multiple of the chunk; and what a decay a channel gives when
    every channel of a head is handed the same number.  The kernel: the
    recurrence, and the `jnp` form it stands for."""
    xs, s0 = _one_decay(T + chunk, T, dims)
    o, s = _recurrent(*xs, s0)
    oc, sc, snap = _compiled(chunk, sub, form=form)(*xs, s0)
    assert snap is None and oc.shape == o.shape
    _close(oc, o)
    _close(sc, s)
    q, k, v, g, beta = xs
    ok, sk, _ = _compiled(chunk, sub)(
        *((q, k, v, jnp.broadcast_to(g, k.shape), beta) if form == "jnp"
          else xs), s0)
    _close(oc, ok)
    _close(sc, sk)


@one_forms
def test_one_decay_a_head_two_calls_are_one(form):
    xs, s0 = _one_decay(7, 90, ONE_FORMS[form])
    chunked = _compiled(16, form=form)
    o, s = chunked(*xs, s0)[:2]
    o1, s1, _ = chunked(*(a[:, :37] for a in xs), s0)
    o2, s2, _ = chunked(*(a[:, 37:] for a in xs), s1)
    _close(jnp.concatenate([o1, o2], axis=1), o)
    _close(s2, s)


@pytest.mark.parametrize("form,chunk,sub", [
    ("jnp", 64, 16), ("jnp", 32, 32), ("kernel", 64, 16)])
def test_one_strong_decay_a_head_overflows_nothing(form, chunk, sub):
    """Decays down to 1e-4 a token: a factored ``exp(-G_j)`` would be
    inf inside one chunk (exp(295) over 32 tokens); the (C, C) mask's
    exponents are sums of non-positive terms."""
    xs, s0 = _one_decay(5, 128, ONE_FORMS[form], decay=(1e-4, 0.9))
    o, s = _recurrent(*xs, s0)
    oc, sc, _ = _compiled(chunk, sub, form=form)(*xs, s0)
    assert bool(jnp.all(jnp.isfinite(oc))) and bool(
        jnp.all(jnp.isfinite(sc)))
    _close(oc, o)
    _close(sc, s)


@pytest.mark.parametrize("pads", [13, 35])
@one_forms
def test_one_decay_a_head_a_pad_is_an_identity_step(form, pads):
    """13 pads inside the first chunk, 35 the first two chunks whole
    and more; and chunks of pads alone leave the state to the bit."""
    (q, k, v, g, beta), s0 = _one_decay(9, 48, ONE_FORMS[form])
    chunked = _compiled(16, form=form)
    real = jnp.arange(48) >= pads
    g_p = jnp.where(real[None, :, None, None], g, 0.0)
    b_p = jnp.where(real[None, :, None], beta, 0.0)
    o, s, _ = chunked(q, k, v, g_p, b_p, s0)
    want_o, want_s = _recurrent(q[:, pads:], k[:, pads:], v[:, pads:],
                                   g[:, pads:], beta[:, pads:], s0)
    _close(o[:, pads:], want_o)
    _close(s, want_s)
    idle = chunked(q, k, v, jnp.zeros_like(g), jnp.zeros_like(beta), s0)[1]
    assert bool(jnp.all(idle == s0))


@pytest.mark.parametrize("form,capture", [
    ("jnp", c) for c in (0, 15, 16, 40, 82)] + [
    ("kernel", c) for c in (0, 15, 40, 79, 82)])
def test_one_decay_a_head_the_captured_state(form, capture):
    """A chunk's first, a middle and its last column, in the first
    chunk, a middle one and the last, which the length does not fill."""
    xs, s0 = _one_decay(11, 83, ONE_FORMS[form])
    want = _recurrent(*(a[:, :capture + 1] for a in xs), s0)[1]
    o, s, snap = _captured(form)(xs, s0, capture)
    _close(snap, want)
    _close(s, _recurrent(*xs, s0)[1])
    _close(o, _recurrent(*xs, s0)[0])


@one_forms
def test_one_decay_a_head_bf16_operands_accumulate_in_float32(form):
    xs, s0 = _one_decay(8, 96, ONE_FORMS[form], decay=(0.9, 0.999))
    o, s = _recurrent(*xs, s0)
    oc, sc, _ = _compiled(64, dtype=jnp.bfloat16, form=form)(*xs, s0)
    assert oc.dtype == sc.dtype == jnp.float32
    _close(oc, o, tol=3e-2)
    _close(sc, s, tol=3e-2)


@one_forms
def test_one_decay_a_head_equal_keys_and_beta_two_stay_bounded(form):
    """`test_equal_keys_and_beta_two_stay_bounded` with one decay a
    head: the transition is a reflection and the solve stays forward
    substitution, in the kernel too."""
    T = 64
    _, heads, dk, dv = ONE_FORMS[form]
    k = jnp.broadcast_to(jnp.eye(dk)[0], (1, T, heads, dk))
    v = jax.random.normal(jax.random.PRNGKey(0), (1, T, heads, dv))
    g = jnp.zeros((1, T, heads, 1))
    beta = jnp.full((1, T, heads), 2.0)
    o, s = _recurrent(k, k, v, g, beta)
    oc, sc, _ = _compiled(64, form=form)(k, k, v, g, beta)
    _close(oc, o)
    _close(sc, s)


def test_one_decay_a_head_groups_of_heads_are_heads_alone(monkeypatch):
    """Two rows of six heads in groups of three: a grid step finds its
    own heads' decays and betas among every head's (the row's columns
    of ``g`` and ``beta`` are fetched whole), and a row its own state."""
    monkeypatch.setattr(kda, "_HEADS_A_GATE_STEP", 3)
    xs, s0 = _one_decay(3, 40, (2, 6, 96, 192))
    oc, sc, snap = kda_chunk(*xs, s0, chunk=16, sub=8, dtype=jnp.float32,
                             capture=jnp.int32(21), interpret=True)
    o, s = _recurrent(*xs, s0)
    _close(oc, o)
    _close(sc, s)
    _close(snap, _recurrent(*(a[:, :22] for a in xs), s0)[1])


#: the published head of a Gated DeltaNet layer: two rows of thirty
#: heads of 96 x 192, a row a grid step
PUBLISHED = (2, 30, 96, 192)
#: the two step forms of one decay a head and the sizes each is run
#: at: the kernel takes keys of whole sublane tiles and values that
#: fill half their lanes, and its cases share one trace of the jitted
#: call (the published head, a stack of three)
ONE_STEP_FORMS = {"jnp": (2, 5, 12, 24), "kernel": PUBLISHED}


@pytest.mark.parametrize("form", sorted(ONE_STEP_FORMS))
def test_a_step_with_one_decay_a_head_is_the_recurrence(form):
    (q, k, v, g, beta), s0 = _one_decay(4, 1, ONE_STEP_FORMS[form])
    o, s = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], s0)
    a = float(np.exp(g[0, 0, 0, 0])) * np.asarray(s0[0, 0], np.float64)
    kk, vv = (np.asarray(x[0, 0, 0], np.float64) for x in (k, v))
    new = a + float(beta[0, 0, 0]) * np.outer(kk, vv - a.T @ kk)
    np.testing.assert_allclose(s[0, 0], new, rtol=1e-5, atol=1e-5)
    stack = jnp.stack([s0, 2 * s0, -s0])
    o1, after = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                           stack, 1, interpret=form == "kernel")
    want_o, want = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            2 * s0)
    _close(o1, want_o)
    _close(after[1], want)
    assert bool(jnp.all(after[0] == s0)) and bool(jnp.all(after[2] == -s0))


@pytest.fixture(scope="module")
def published_wave():
    """One wave at the published head through the kernel for ONE decay
    a head (interpreted, run once for the module's cases): the middle
    layer of a stack of three, row 0 a pad (``g`` = 0, ``beta`` = 0).
    (the wave's operands, the stack, o, the stack after)."""
    (q, k, v, g, beta), _ = _one_decay(21, 1, PUBLISHED)
    pad = jnp.arange(PUBLISHED[0]) == 0
    wave = (q[:, 0], k[:, 0], v[:, 0],
            jnp.where(pad[:, None, None], 0.0, g[:, 0]),
            jnp.where(pad[:, None], 0.0, beta[:, 0]))
    stack = jax.random.normal(jax.random.PRNGKey(3), (3, *PUBLISHED))
    return wave, stack, *kda_decode(*wave, stack, 1, interpret=True)


def test_the_head_gate_step_kernel_is_kda_step_at_the_published_head(
        published_wave):
    """Thirty heads of 96 x 192, a row a grid step: the layer it names
    is `kda_step` on its slice, the two others come back TO THE BIT."""
    wave, stack, o, after = published_wave
    want_o, want = kda_step(*wave, stack[1])
    assert o.shape == want_o.shape and after.shape == stack.shape
    assert o.dtype == after.dtype == jnp.float32
    _close(o, want_o)
    _close(after[1], want)
    for other in (0, 2):
        assert bool(jnp.all(after[other] == stack[other]))


def test_the_head_gate_step_kernel_leaves_a_padded_row_to_the_bit(
        published_wave):
    """``g`` = 0 and ``beta`` = 0 (a decode pool's row without a
    sequence), beside a row that moves."""
    _, stack, _, after = published_wave
    assert bool(jnp.all(after[1, 0] == stack[1, 0]))
    assert not bool(jnp.all(after[1, 1] == stack[1, 1]))


@pytest.mark.parametrize("dims,channel,dtype", [
    ((1, 5, 96, 192), False, jnp.bfloat16),     # a state that is no float32
    ((1, 5, 96, 192), True, jnp.float32),       # a decay a channel
    ((1, 5, 12, 64), False, jnp.float32),       # keys of a tile and a half
    ((1, 5, 96, 24), False, jnp.float32),       # values of 24 in 128 lanes
], ids=["bf16_state", "a_decay_a_channel", "keys_of_12", "values_of_24"])
def test_the_head_gate_step_kernel_runs_only_where_it_fits(dims, channel,
                                                           dtype):
    """One shape a branch of `_fits_the_head_gate_step_kernel`: each
    falls back to `kda_step` on the layer indexed out and set back,
    asked for interpreted or not, and gives what that gives."""
    (q, k, v, g, beta), s0 = _inputs(2, 1, dims=dims)
    wave = (q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, :None if channel else 1],
            beta[:, 0])
    stack = jnp.stack([s0, 2 * s0]).astype(dtype)
    assert not kda._fits_the_head_gate_step_kernel(stack, wave[3])
    assert kda._fits_the_head_gate_step_kernel(
        jnp.zeros((2, 1, 5, 96, 192)), jnp.zeros((1, 5, 1)))
    assert kda._step_call_for(stack, wave[3]) is None
    program = jax.jit(lambda stack: (
        kda_decode(*wave, stack, 1, interpret=True),
        kda_step(*wave, stack[1]))).trace(stack)
    assert "pallas_call" not in str(program.jaxpr)
    (o, after), (want_o, want) = program.lower().compile()(stack)
    assert after.dtype == dtype
    _close(o, want_o)
    _close(after[1].astype(jnp.float32), want.astype(dtype).astype(
        jnp.float32))
    assert bool(jnp.all(after[0] == stack[0]))


def test_the_head_gate_step_kernel_groups_of_heads_are_heads_alone(
        monkeypatch):
    """Two rows of six heads where a grid step's bytes hold four: groups
    of three, and a grid step finds its own group's rows and
    matrices."""
    monkeypatch.setattr(kda, "_GATE_WAVE_BLOCK", 4 * 8 * 128 * 4)
    (q, k, v, g, beta), s0 = _one_decay(6, 1, (2, 6, 8, 64))
    wave = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    # (the form jitted anew: the module's jitted call keeps the traces
    # it has made, a row a step)
    o, after = jax.jit(kda._head_gate_step_form, static_argnums=(7,))(
        *wave, s0[None], jnp.int32(0), True)
    want_o, want = kda_step(*wave, s0)
    _close(o, want_o)
    _close(after[0], want)


def test_a_decay_a_channel_never_enters_the_matmul_form(monkeypatch):
    """KDA's results are today's to the bit because its path is
    today's: a ``g`` of trailing size ``dk`` walks `_chunk`, only one of
    trailing size 1 the matmul form, and on the chip (steered here) it
    takes its own kernel, ``kda_chunk``, never the one for one decay a
    head.  A fitting prefill with ONE decay a head takes that kernel,
    ``delta_chunk``, on the chip and the matmul form off it; one column
    and heads of 12 x 24 keep their `jnp` forms.  A decode wave picks
    among three the same way: off the chip and differentiated the `jnp`
    step, a decay a channel the call ``kda_decode``, ONE decay a head
    the call ``delta_decode``.  A differentiated call is `kda_chunked`,
    forward and backward.  (All traced, nothing run.)"""
    entered = []
    real = kda._scalar_gate_chunked
    monkeypatch.setattr(kda, "_scalar_gate_chunked", lambda *a: (
        entered.append(a[3].shape) or real(*a)))

    def traced(form, xs, s0):
        return jax.eval_shape(functools.partial(
            form, chunk=16, dtype=jnp.float32), *xs, s0)

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn.params["name"]
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(inner)

    def kernels(form, xs, s0, **kw):
        """The names of the Pallas calls `form` traces to."""
        return sorted(calls(jax.make_jaxpr(functools.partial(
            form, chunk=16, dtype=jnp.float32, **kw))(*xs, s0).jaxpr))

    def steps(xs, s0):
        """The names of the Pallas calls a decode wave's step traces
        to."""
        return sorted(calls(jax.make_jaxpr(lambda stack: kda_decode(
            *(a[:, 0] for a in xs), stack, 0))(s0[None]).jaxpr))

    traced(kda_chunked, *_inputs(2, 24))
    assert not entered
    traced(kda_chunked, *_one_decay(2, 24))
    assert entered == [(1, 24, 5, 1)]
    channel = _inputs(2, 24, dims=(1, 8, 128, 128))
    gate = _one_decay(2, 24, GATE)
    assert kernels(kda_prefill, *gate) == []                # the CPU
    assert steps(*gate) == steps(*channel) == []
    assert entered[-1] == (1, 24, 5, 1)
    del entered[:]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kernels(kda_prefill, *channel) == ["kda_chunk"]
    assert kernels(kda_chunk, *channel, interpret=True) == ["kda_chunk"]
    assert not entered
    assert kernels(kda_prefill, *gate) == ["delta_chunk"]
    assert not entered      # no column is captured: nothing done again
    xs, s0 = gate
    assert kernels(kda_prefill, tuple(a[:, :1] for a in xs), s0) == []
    small = _one_decay(2, 24)
    assert kernels(kda_prefill, *small) == []               # 12 x 24
    assert [shape[1:] for shape in entered] == [(1, 5, 1), (24, 5, 1)]
    # a decode wave picks among three: a decay a channel the call
    # ``kda_decode``, ONE decay a head the call ``delta_decode``, heads
    # of 12 x 24 the `jnp` step
    assert steps(*channel) == ["kda_decode"]
    assert steps(*gate) == ["delta_decode"]
    assert steps(*small) == []
    with pytest.raises(ValueError, match="do not fit the kernel"):
        kda_chunk(*small[0], small[1], chunk=16, interpret=True)

    def loss(form, v):
        q, k, _, g, beta = xs
        o, s, _ = form(q, k, v, g, beta, s0, chunk=16, dtype=jnp.float32)
        return jnp.sum(o * o) + jnp.sum(s)

    text = str(jax.make_jaxpr(jax.grad(functools.partial(
        loss, kda_prefill)))(xs[2]))
    assert "pallas_call" not in text and "scan" in text

    def step_loss(v):
        q, k, _, g, beta = (a[:, 0] for a in xs)
        o, stack = kda_decode(q, k, v, g, beta, s0[None], 0)
        return jnp.sum(o * o) + jnp.sum(stack)

    assert list(calls(jax.make_jaxpr(jax.grad(step_loss))(
        xs[2][:, 0]).jaxpr)) == []
