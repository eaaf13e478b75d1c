"""The ring decode kernel (ray_tpu/ops/ring_decode.py) on the CPU, in
the Pallas interpreter, against `attend_rows` over `_ring_mask`; and
the two decode steps that take it on the chip against their CPU
paths."""

import functools
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models import banded_attention as BA
from ray_tpu.models import experts
from ray_tpu.models import laguna_decode as D
from ray_tpu.models import phi4flash_decode as P
from ray_tpu.models.laguna import laguna_config, laguna_init
from ray_tpu.models.phi4flash import phi4flash_config, phi4flash_init
from ray_tpu.ops.ring_decode import fits_the_kernel, ring_decode
from tests.test_gqa_paged_decode import _up
from tests.test_mla import BF16_RMS, F32_ATOL
from tests.test_mla_paged_decode import _named
from tests.test_ssm_scan import _count

W = 32
#: (query heads, K/V heads, head size, the scores' factor): Laguna's
#: window layers, and differential attention's pair-heads
#: (models/phi4flash.py `Phi4FlashConfig.pairs`: the published 40
#: padded query sub-heads over 10 K/V pair-heads of 128 lanes, a group
#: of 4, scale 1 / sqrt(64))
GEOMETRY = {"laguna_window": (64, 8, 128, None),
            "phi4flash_pairs": (40, 10, 128,
                                phi4flash_config().pairs.scale)}
#: one wave each: every row's ``pos`` AFTER which its new row is in the
#: ring, and its ``start``
WAVES = {
    # a ring partly filled: the rows behind ``pos`` are a previous
    # tenant's and derive to slots below 0
    "partly_filled": ([1, 5, W - 1, 17], [0, 0, 0, 0]),
    # wrapped: the newest row at 0, at 1 and at the ring's last row
    "wrapped": ([W, W + 1, 2 * W - 1, 5 * W, 3 * W + 1], [0] * 5),
    # a first slot above 0: inside the ring's reach, behind it, the
    # newest row alone
    "a_first_slot": ([W + 5, 2 * W + 3, 9, 4 * W + 7],
                     [W - 3, 3, 4, 4 * W + 7]),
    # rows without a sequence between rows with one
    "idle_rows": ([0, W + 3, 0, 7], [0, 0, 0, 0]),
}


def _wave(geometry, wave, dtype, seed=0, n_window=3):
    """Random stacked rings and one decode column's q: (q, wk, wv, pos,
    start), the geometry as a config `attend_rows` reads, its scale."""
    H, n_kv, hd, scale = GEOMETRY[geometry]
    pos, start = (jnp.asarray(a, jnp.int32) for a in WAVES[wave])
    B = len(WAVES[wave][0])
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    rand = lambda k, *shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    cfg = types.SimpleNamespace(n_kv_head=n_kv, head_dim=hd, dtype=dtype)
    return ((rand(ks[0], B, H, hd), rand(ks[1], n_window, B, W, n_kv * hd),
             rand(ks[2], n_window, B, W, n_kv * hd), pos, start), cfg, scale)


def _kernel(q, wk, wv, j, pos, start, cfg, scale):
    return ring_decode(
        q, wk, wv, j, pos, start, n_kv_head=cfg.n_kv_head,
        scale=1.0 / math.sqrt(cfg.head_dim) if scale is None else scale,
        interpret=True)


def _oracle(q, wk, wv, j, pos, start, cfg, scale):
    return BA.attend_rows(q, wk[j], wv[j],
                         BA._ring_mask(pos, start, wk.shape[2]), cfg, scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("wave", WAVES)
@pytest.mark.parametrize("geometry", GEOMETRY)
def test_a_wave_through_the_kernel_is_attend_rows_over_the_ring_mask(
        geometry, wave, dtype):
    """Both families' geometries over rings partly filled, wrapped with
    the newest row at 0, 1 and ``window - 1``, with a first slot above
    0 and with idle rows, in the middle layer of three: the kernel is
    `attend_rows` over `_ring_mask`, a previous tenant's rows never
    attended (they are poisoned below)."""
    (q, wk, wv, pos, start), cfg, scale = _wave(geometry, wave, dtype)
    got = np.asarray(_kernel(q, wk, wv, 1, pos, start, cfg, scale),
                     np.float32)
    assert got.shape == q.shape
    if dtype == jnp.float32:
        want = np.asarray(_oracle(q, wk, wv, 1, pos, start, cfg, scale))
        np.testing.assert_allclose(got, want, atol=F32_ATOL)
        # what the mask hides is never attended: huge keys and values
        # there change nothing
        hidden = ~BA._ring_mask(pos, start, W)[None, :, :, None]
        layer = (jnp.arange(3) == 1)[:, None, None, None]
        got_again = _kernel(q, jnp.where(hidden & layer, 1e4, wk),
                            jnp.where(hidden & layer, -1e4, wv), 1, pos,
                            start, cfg, scale)
        np.testing.assert_allclose(np.asarray(got_again), got,
                                   atol=F32_ATOL)
        return
    # bf16 against the same inputs attended in float32: the RMS
    # tests/test_mla.py states for bf16 compute, and no element further
    # off than bf16's probabilities and result round (a row of few keys
    # returns values of order 1, not a long context's average)
    up = types.SimpleNamespace(**dict(vars(cfg), dtype=jnp.float32))
    want = np.asarray(_oracle(*_up((q, wk, wv)), 1, pos, start, up, scale))
    err = np.abs(got - want)
    assert np.sqrt(np.mean(err ** 2)) < BF16_RMS
    assert err.max() <= 2 ** -6 * np.abs(want).max()


@pytest.mark.parametrize("geometry", GEOMETRY)
def test_the_kernel_is_bf16_attend_rows_to_a_rounding(geometry):
    """bf16 against `attend_rows` in bf16, the path it replaces on the
    chip: the same operations in the same precision, so the two differ
    by a rounding of the result and no more."""
    (q, wk, wv, pos, start), cfg, scale = _wave(geometry, "wrapped",
                                                jnp.bfloat16)
    got = np.asarray(_kernel(q, wk, wv, 2, pos, start, cfg, scale),
                     np.float32)
    want = np.asarray(_oracle(q, wk, wv, 2, pos, start, cfg, scale),
                      np.float32)
    assert np.abs(got - want).max() <= 2 ** -7 * np.abs(want).max()


@pytest.mark.parametrize("j", [0, 2], ids=["first_layer", "last_layer"])
@pytest.mark.parametrize("geometry", GEOMETRY)
def test_the_layer_is_a_traced_index_inside_a_scan(geometry, j):
    """The layer a ``lax.scan``'s traced counter, as the Phi-4-flash
    decode step has it: layer `j` alone is read (the others are
    poisoned), and the stacks come back bit for bit."""
    (q, wk, wv, pos, start), cfg, scale = _wave(geometry, "idle_rows",
                                                jnp.float32)
    want = _oracle(q, wk, wv, j, pos, start, cfg, scale)
    others = (jnp.arange(3) != j)[:, None, None, None]
    wk, wv = (jnp.where(others, jnp.nan, r) for r in (wk, wv))

    @jax.jit
    def scanned(wk, wv):
        def body(carry, i):
            wk, wv = carry
            o = _kernel(q, wk, wv, i, pos, start, cfg, scale)
            return (wk, wv), o
        (wk, wv), outs = lax.scan(body, (wk, wv),
                                  jnp.arange(3, dtype=jnp.int32))
        return outs, wk, wv

    outs, wk_after, wv_after = scanned(wk, wv)
    np.testing.assert_allclose(np.asarray(outs[j]), np.asarray(want),
                               atol=F32_ATOL)
    np.testing.assert_array_equal(np.asarray(wk_after), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(wv_after), np.asarray(wv))


def test_which_shapes_fit_the_kernel():
    """Heads and folded rows of whole lanes, a window of whole sublane
    tiles; nano's heads of 16 and windows of 8 keep the ``jnp`` path."""
    q = jnp.zeros((2, 8, 128))
    assert fits_the_kernel(q, jnp.zeros((3, 2, 512, 1024)))
    assert fits_the_kernel(q, jnp.zeros((3, 2, 16, 256)))
    assert not fits_the_kernel(q, jnp.zeros((3, 2, 8, 256)))
    assert not fits_the_kernel(jnp.zeros((2, 8, 16)),
                               jnp.zeros((3, 2, 16, 32)))


# -- the decode steps that take the kernel -----------------------------------

def _steer(monkeypatch):
    """The decode steps take the chip's paths (the backend test says
    "tpu") and every kernel on them runs in the interpreter."""
    from ray_tpu.ops.gqa_paged_decode import gqa_paged_decode

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(BA, "ring_decode", functools.partial(
        ring_decode, interpret=True))
    monkeypatch.setattr(BA, "gqa_paged_decode", functools.partial(
        gqa_paged_decode, interpret=True))
    for kernel in ("moe_dispatch", "moe_combine", "_fused"):
        monkeypatch.setattr(experts, kernel, functools.partial(
            getattr(experts, kernel), interpret=True))


BS = 16


def _laguna():
    """nano at heads of 128 lanes and a window of 16 rows: the kernel's
    shapes at a size the interpreter walks."""
    cfg = laguna_config("nano", head_dim=128, window=16, max_seq=64,
                        dtype=jnp.float32)
    return types.SimpleNamespace(
        cfg=cfg, params=laguna_init(jax.random.PRNGKey(0), cfg),
        step=D.laguna_decode_step, prefill=D.laguna_paged_prefill,
        dense_prefill=D.laguna_prefill,
        paged=functools.partial(D.laguna_init_paged_cache, cfg),
        n_window=len(cfg.layers_of("window")))


def _phi4flash():
    """nano at pair-heads of 128 lanes (heads of 64) and a window of 16
    rows."""
    cfg = phi4flash_config("nano", d_model=256, n_head=4, n_kv_head=2,
                           window=16, max_seq=64, dtype=jnp.float32)
    return types.SimpleNamespace(
        cfg=cfg, params=phi4flash_init(jax.random.PRNGKey(0), cfg),
        step=P.phi4flash_decode_step, prefill=P.phi4flash_paged_prefill,
        dense_prefill=P.phi4flash_prefill,
        paged=functools.partial(P.phi4flash_init_paged_cache, cfg),
        n_window=cfg.n_self)


FAMILIES = {"laguna": _laguna, "phi4flash": _phi4flash}


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    return FAMILIES[request.param]()


def _prefilled(fam):
    """Two rows prefilled into the pool, one shorter than the window
    and one that wrapped it, a row idle between them."""
    cache = fam.paged(3, num_blocks=13, block_size=BS)
    rng = np.random.RandomState(3)
    for slot, n in ((0, 7), (2, 27)):
        toks = np.zeros((1, 32), np.int32)
        toks[0, 32 - n:] = rng.randint(2, 500, n)
        row_bt = np.zeros((fam.cfg.max_seq // BS,), np.int32)
        row_bt[:4] = 1 + 4 * slot + np.arange(4)
        _, cache = jax.jit(functools.partial(
            fam.prefill, cfg=fam.cfg, prefix_len=0, n_tail=n, slot=slot))(
                fam.params, cache, jnp.asarray(toks),
                row_bt=jnp.asarray(row_bt))
    return cache


@pytest.mark.parametrize("backend,kernels", [("cpu", 0), ("tpu", 1)])
def test_only_the_chips_decode_step_holds_the_kernel(family, monkeypatch,
                                                     backend, kernels):
    """A decode step on the TPU backend takes one ``ring_decode`` a
    window layer (a scan's body holds its one), in both cache layouts;
    the CPU keeps `attend_rows`."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    fam = family
    tokens = jnp.ones((3,), jnp.int32)
    dense = jax.eval_shape(
        lambda: fam.dense_prefill(fam.params, jnp.ones((3, 8), jnp.int32),
                                  fam.cfg))[1]
    for cache in (fam.paged(3, num_blocks=13, block_size=BS), dense):
        jaxpr = jax.make_jaxpr(lambda c: fam.step(
            fam.params, c, tokens, fam.cfg))(cache).jaxpr
        calls = _named(jaxpr, scopes.RING_DECODE)
        assert calls == kernels * (
            fam.n_window if fam.step is D.laguna_decode_step else 1), calls
        if not kernels:
            assert _named(jaxpr, scopes.GQA_PAGED_DECODE) == 0
        assert (_count(jaxpr, "pallas_call") > 0) == bool(kernels)


def test_the_decode_step_through_the_kernel_is_the_cpu_step(family,
                                                            monkeypatch):
    """Prefill two rows (one short of the window, one wrapped), leave
    one idle, then three decode steps by both paths: the live rows'
    logits and the whole cache agree, the idle row's rings bit for
    bit."""
    fam = family
    cache = _prefilled(fam)
    assert cache["pos"].tolist() == [7, 0, 27]
    tokens = jnp.asarray([5, 0, 7], jnp.int32)

    def three_steps():
        step = jax.jit(lambda c: fam.step(fam.params, c, tokens, fam.cfg))
        logits, after = [], cache
        for _ in range(3):
            out, after = step(after)
            logits.append(out)
        return logits, after

    want_logits, want = three_steps()
    _steer(monkeypatch)
    got_logits, got = three_steps()
    live = np.asarray([0, 2])
    for g, w in zip(got_logits, want_logits):
        np.testing.assert_allclose(np.asarray(g)[live], np.asarray(w)[live],
                                   atol=20 * F32_ATOL)
    assert got["pos"].tolist() == [10, 0, 30]
    assert sorted(got) == sorted(want)
    for name in want:
        np.testing.assert_allclose(
            np.asarray(got[name], np.float32),
            np.asarray(want[name], np.float32), atol=20 * F32_ATOL,
            err_msg=name)
    for name in ("wk", "wv"):
        np.testing.assert_array_equal(np.asarray(got[name][:, 1]),
                                      np.asarray(cache[name][:, 1]))
