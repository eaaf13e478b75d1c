"""ops/ssm_scan.py: the selective-scan kernel against the ``jnp`` chain.

On the CPU, the kernel in the Pallas interpreter (``interpret=True``,
asked for here by argument: the program itself never picks it).  What
the chip's compiler says of the kernel is tests/test_tpu_compile.py's;
what the chip says, chip_smoke.py's.

Limits.  Kernel and chain run the same float32 operations in the same
order on every element of the state (``exp(dt A)``, ``(dt x) B``, ``a s
+ bx``), so states agree to the last bit or two; an output is a sum
over ``d_state`` that the two may associate differently.  Both are held
to tests/test_jamba.py's own float32 limit, ``F32_ATOL`` 5e-6 on values
of order one (measured here: 0 to 2.4e-7 on states, 1e-7 relative on
outputs); a state kept in bfloat16 would miss it by 5e-5 to 7e-5.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import jamba, jamba_decode
from ray_tpu.models.jamba import jamba_config, jamba_init, jamba_loss
from ray_tpu.ops import ssm_scan as kernel_module
from ray_tpu.ops.ssm_scan import selective_scan, selective_scan_reference

F32_ATOL = 5e-6          # tests/test_jamba.py's
N = 16

_kernel = functools.partial(selective_scan, interpret=True)
_chain = jax.jit(selective_scan_reference, static_argnums=(6,))


def _operands(B, T, di, seed):
    rng = np.random.RandomState(seed)
    f32 = lambda a: jnp.asarray(a, jnp.float32)  # noqa: E731
    return (f32(rng.uniform(0.01, 1.0, (B, T, di))),       # dt
            f32(rng.randn(B, T, di)),                      # x
            -f32(rng.uniform(0.5, 4.0, (N, di))),          # A
            f32(rng.randn(B, T, N)), f32(rng.randn(B, T, N)),
            f32(rng.randn(B, N, di)))                      # s0, not zero


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    limit = F32_ATOL * max(1.0, float(np.abs(want).max()))
    np.testing.assert_allclose(got, want, atol=limit, rtol=0)


# (B, T, d_inner, left pads, captured column).  The kernel takes the
# widest block of 512, 256 or 128 lanes that divides d_inner: 384 is
# three blocks of 128 with two edges inside, 128, 256 and 512 one block
# with its edge at d_inner, 192 one of 256 with idle lanes, 1,024 and
# 1,536 two and three blocks of 512 (the published width's block).
# T = 640 is five chunks of 128 columns (edges after columns 127, 255,
# ...), the shorter ones one chunk padded with identity columns, walked
# in groups of eight.
CASES = [
    (1, 2, 128, 0, 1), (3, 2, 384, 1, 0),
    (1, 31, 128, 5, 30), (3, 31, 384, 0, 7),
    (1, 32, 384, 3, 8), (3, 32, 192, 0, 31),
    (1, 33, 256, 27, 32), (3, 33, 128, 1, 15),
    (1, 640, 384, 100, 126),           # before a chunk's edge
    (1, 640, 384, 100, 127),           # at it: the chunk's last column
    (1, 640, 384, 100, 128),           # after it: the next one's first
    (1, 640, 384, 0, 639),             # T - 1
    (3, 640, 128, 37, 384),
    (3, 130, 512, 0, 129),
    (1, 33, 1024, 2, 20),
    (1, 136, 1536, 3, 130),            # blocks of 512 over two chunks
]


@pytest.mark.parametrize("B,T,di,pads,capture", CASES)
def test_kernel_matches_the_chain(B, T, di, pads, capture):
    """Outputs, final state and captured state of a left-padded batch
    from a non-zero state; and the pads are identity columns to the bit:
    the same rows without them end in the same states."""
    dt, x, A, Bm, Cm, s0 = _operands(B, T, di, seed=T + pads)
    real = (jnp.arange(T) >= pads)[None, :, None]
    dt, x = jnp.where(real, dt, 0.0), jnp.where(real, x, 0.0)
    cap = jnp.int32(capture)
    y, s, snap = _kernel(dt, x, A, Bm, Cm, s0, cap)
    want_y, want_s, want_snap = _chain(dt, x, A, Bm, Cm, s0, 8, cap)
    assert y.shape == (B, T, di) and s.shape == snap.shape == (B, N, di)
    _close(y[:, pads:], want_y[:, pads:])
    _close(s, want_s)
    _close(snap, want_snap)
    if pads and capture >= pads:
        cut = lambda a: a[:, pads:]  # noqa: E731
        y0, s0_, snap0 = _kernel(cut(dt), cut(x), A, cut(Bm), cut(Cm), s0,
                                 cap - pads)
        np.testing.assert_array_equal(np.asarray(y[:, pads:]),
                                      np.asarray(y0))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s0_))
        np.testing.assert_array_equal(np.asarray(snap), np.asarray(snap0))


def test_without_a_capture_there_is_no_snapshot():
    ops = _operands(2, 19, 128, seed=3)
    y, s, snap = _kernel(*ops)
    want_y, want_s, _ = _chain(*ops, 8)
    assert snap is None
    _close(y, want_y)
    _close(s, want_s)


# -- which programs take the kernel ------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = jamba_config("nano", dtype=jnp.float32, remat=False)
    return cfg, jamba_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def on_tpu(monkeypatch):
    """The backend test says "tpu".  Tracing only: nothing compiled for
    a chip that is not there."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _count(jaxpr, primitive: str) -> int:
    """Equations of `primitive` in a jaxpr, those of its sub-jaxprs
    (scan bodies, jit calls) included."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == primitive
        for sub in jax.core.jaxprs_in_params(eqn.params):
            total += _count(sub, primitive)
    return total


def _kernels_traced(fn, *args) -> int:
    return _count(jax.make_jaxpr(fn)(*args).jaxpr, "pallas_call")


def _paged_cache(cfg):
    return jamba_decode.jamba_init_paged_cache(cfg, 3, num_blocks=17,
                                               block_size=16)


def _programs(cfg, params):
    """name -> (traceable function, arguments) of the family's four
    programs at the tiny size."""
    tokens = jnp.ones((1, 32), jnp.int32)
    row_bt = jnp.zeros((cfg.max_seq // 16,), jnp.int32)
    return {
        "decode_step": (
            lambda c, t: jamba_decode.jamba_decode_step(params, c, t, cfg),
            (_paged_cache(cfg), jnp.ones((3,), jnp.int32))),
        "paged_prefill": (
            lambda c, t: jamba_decode.jamba_paged_prefill(
                params, c, t, cfg, row_bt=row_bt, prefix_len=0, n_tail=20,
                slot=1),
            (_paged_cache(cfg), tokens)),
        "dense_prefill": (
            lambda t: jamba_decode.jamba_prefill(
                params, t, cfg, lengths=jnp.asarray([20, 32])),
            (jnp.ones((2, 32), jnp.int32),)),
        "hidden": (lambda t: jamba.jamba_hidden(params, t, cfg),
                   (jnp.ones((2, 32), jnp.int32),)),
    }


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_a_decode_step_never_holds_the_kernel(tiny, monkeypatch, backend):
    """One column over every slot is bound by the state's bytes: the
    decode step keeps the single elementwise expression whatever the
    backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    fn, args = _programs(*tiny)["decode_step"]
    assert _kernels_traced(fn, *args) == 0


@pytest.mark.parametrize("program", ["paged_prefill", "dense_prefill",
                                     "hidden"])
def test_a_prefill_holds_one_kernel_a_mamba_walk(tiny, on_tpu, program):
    """Every program with more than one column: one ``pallas_call`` in
    each scan over Mamba layers.  The nano pattern (attention third of
    four) has a walk before the attention layer and one after it."""
    fn, args = _programs(*tiny)[program]
    assert _kernels_traced(fn, *args) == 2


@pytest.mark.parametrize("program", ["paged_prefill", "hidden"])
def test_off_the_tpu_the_chain_stays(tiny, program):
    assert jax.default_backend() == "cpu"
    fn, args = _programs(*tiny)[program]
    assert _kernels_traced(fn, *args) == 0


def test_loss_gradient_through_the_kernel_is_the_chains(tiny, monkeypatch):
    """``jax.grad(jamba_loss)`` with the kernel in the forward pass (its
    backward is the chain's VJP) against the chain alone, which is the
    parent's program."""
    cfg, params = tiny
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(5).randint(2, 500, (2, 25)), jnp.int32)}
    loss = lambda p: jamba_loss(p, batch, cfg)  # noqa: E731
    want_loss, want = jax.value_and_grad(loss)(params)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(kernel_module, "selective_scan", functools.partial(
        selective_scan, interpret=True))
    assert _kernels_traced(jax.grad(loss), params) >= 2
    got_loss, got = jax.value_and_grad(loss)(params)
    assert abs(float(got_loss) - float(want_loss)) < F32_ATOL
    flat_got, flat_want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for g, w in zip(flat_got, flat_want):
        _close(g, w)
