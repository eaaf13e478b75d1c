"""The paged grouped-query decode kernel (ray_tpu/ops/gqa_paged_decode.py)
on the CPU, in the Pallas interpreter, against its ``jnp`` reference
over the gathered views; and which Laguna programs take it."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu._private import scopes
from ray_tpu.models import banded_attention
from ray_tpu.models import experts
from ray_tpu.models import laguna_decode as D
from ray_tpu.models.laguna import laguna_config, laguna_init
from ray_tpu.ops.gqa_paged_decode import (_CHUNK, gqa_paged_decode,
                                          gqa_paged_decode_reference)
from tests.test_mla import BF16_RMS, BF16_TOKEN_MEDIAN, F32_ATOL
from tests.test_mla_paged_decode import _named, _shapes, _tables
from tests.test_ssm_scan import _count

BS = 16
EDGE = _CHUNK * BS            # positions a chunk


def _steer(monkeypatch):
    """The decode step takes the kernel's path (the backend test says
    "tpu") and every kernel on it runs in the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(banded_attention, "gqa_paged_decode", functools.partial(
        gqa_paged_decode, interpret=True))
    # the chip's step moves the experts' rows by kernels too, and
    # multiplies them by one where they are few an expert
    for kernel in ("moe_dispatch", "moe_combine", "_fused"):
        monkeypatch.setattr(experts, kernel, functools.partial(
            getattr(experts, kernel), interpret=True))


@pytest.fixture
def interpreted(monkeypatch):
    _steer(monkeypatch)


# one wave each: the rows' lengths, the tables' kind, max_seq, and
# (query heads, K/V heads, head size)
NANO, ODD, PUBLISHED = (6, 2, 16), (9, 3, 8), (48, 8, 128)
#: differential attention's pair-heads (models/phi4flash.py): 40 padded
#: query sub-heads over 10 K/V pair-heads of 128 lanes, a group of 4
PAIRS = (40, 10, 128)
#: multi-head attention (models/olmo_hybrid.py): as many K/V heads as
#: query heads, a group of ONE padded to a sublane tile, rows of 3,840
#: lanes
EVERY_HEAD_ITS_OWN = (30, 30, 128)
WAVES = {
    "ragged": ([1, 16, 17, 0, 100, 300, EDGE + 200], "out_of_order",
               1024, NANO),
    # the last: a row stepped past its table's end (a wave queued behind
    # the row's last): every slot attended, none past the table walked
    "block_edges_shared": ([16, 32, 48, 33, 15, 64, 133], "shared", 128,
                           NANO),
    "every_row_idle": ([0, 0, 0], "in_order", 128, NANO),
    "a_chunks_edge": ([EDGE - 1, EDGE, EDGE + 1, 2 * EDGE, 2 * EDGE + 1],
                      "shared", 2048, NANO),
    "an_odd_grouping": ([5, 0, EDGE + 3, 77], "out_of_order", 1024, ODD),
    "published_heads": ([EDGE + 1, 0, 40], "out_of_order", 1024,
                        PUBLISHED),
    "pair_heads": ([EDGE + 17, 0, 33, 512], "shared", 1024, PAIRS),
    "a_group_of_one": ([EDGE + 5, 0, 37], "out_of_order", 1024,
                       EVERY_HEAD_ITS_OWN),
}


def _wave(name, dtype, seed=0, n_full=2):
    """Random pools, tables, and one decode column's q and fresh rows:
    (args of the kernel and its reference up to `f`, fresh, keywords)."""
    lengths, kind, max_seq, (H, n_kv, hd) = WAVES[name]
    rng = np.random.default_rng(seed)
    B, nb = len(lengths), max_seq // BS
    blocks = (2 if kind == "in_order" else 1) * nb + 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    rand = lambda k, *shape: jax.random.normal(  # noqa: E731
        k, shape, jnp.float32).astype(dtype)
    kpool = rand(ks[0], n_full, blocks, BS, n_kv * hd)
    vpool = rand(ks[1], n_full, blocks, BS, n_kv * hd)
    tables = jnp.asarray(_tables(rng, kind, B, nb, blocks), jnp.int32)
    fresh = (rand(ks[3], B, n_kv * hd), rand(ks[4], B, n_kv * hd))
    return ((rand(ks[2], B, H, hd), kpool, vpool, tables,
             jnp.asarray(lengths, jnp.int32)), fresh,
            dict(n_kv_head=n_kv, scale=hd ** -0.5))


def _up(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
        tree)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("wave", [w for w in WAVES
                                  if w != "every_row_idle"])
def test_a_wave_through_the_kernel_is_the_gathered_views(wave, dtype):
    """Rows of length 1, one under, on and one over a block's and a
    chunk's edge, past the table, rows without a sequence, each with its
    fresh row, over tables out of order and shared between rows, in the
    second layer of the pools; 6 query heads over 2 K/V heads, 9 over 3,
    the published 48 over 8 of 128, and 40 over 10 of 128 (a group of
    4: 1,280 lanes a row)."""
    args, fresh, kw = _wave(wave, dtype)
    got = np.asarray(gqa_paged_decode(*args, 1, fresh, interpret=True,
                                      **kw), np.float32)
    assert got.shape == args[0].shape
    if dtype == jnp.float32:
        want = np.asarray(gqa_paged_decode_reference(*args, 1, fresh, **kw))
        np.testing.assert_allclose(got, want, atol=F32_ATOL)
        return
    # bf16 against the same inputs attended in float32: the tolerance
    # tests/test_mla.py states for bf16 compute
    want = np.asarray(gqa_paged_decode_reference(*_up(args), 1, _up(fresh),
                                                 **kw))
    err = np.abs(got - want)
    assert np.sqrt(np.mean(err ** 2)) < BF16_RMS
    assert np.median(err.reshape(len(err), -1).max(-1)) \
        < BF16_TOKEN_MEDIAN


@pytest.mark.parametrize("f", [0, 1])
def test_the_layer_is_a_scalar_of_the_call(f):
    """Layer `f` of the pools, traced: the other layer's blocks are not
    read (they are poisoned here)."""
    args, fresh, kw = _wave("ragged", jnp.float32)
    want = gqa_paged_decode_reference(*args, f, fresh, **kw)
    q, kpool, vpool, tables, pos = args
    kpool, vpool = (p.at[1 - f].set(jnp.nan) for p in (kpool, vpool))
    got = jax.jit(lambda f: gqa_paged_decode(
        q, kpool, vpool, tables, pos, f, fresh, interpret=True, **kw))(
            jnp.int32(f))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_ATOL)


def test_a_row_without_a_sequence_returns_its_fresh_value():
    """``pos == 0``: nothing walked, the fresh key's weight is 1, as the
    masked path has it."""
    args, fresh, kw = _wave("every_row_idle", jnp.float32)
    got = gqa_paged_decode(*args, 0, fresh, interpret=True, **kw)
    H, n_kv, hd = WAVES["every_row_idle"][3]
    want = jnp.repeat(fresh[1].reshape(-1, n_kv, hd), H // n_kv, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_ATOL)


def test_the_kernel_honours_a_first_slot():
    """`start` is 0 for every row of a paged cache today; the kernel
    masks the slots before it all the same, as `slot_mask` does."""
    args, fresh, kw = _wave("ragged", jnp.float32)
    start = jnp.asarray([0, 3, 16, 0, 99, 1, EDGE + 1], jnp.int32)
    got = gqa_paged_decode(*args, 1, fresh, start=start, interpret=True,
                           **kw)
    want = gqa_paged_decode_reference(*args, 1, fresh, start=start, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=F32_ATOL)
    moved = gqa_paged_decode_reference(*args, 1, fresh, **kw)
    assert np.abs(np.asarray(moved) - np.asarray(want)).max() > 1e-3


def test_a_rows_walk_ends_at_its_own_context():
    """Poison (NaN) every block of the pools but those under a row's
    ``pos``: the blocks a short row's table names past its context, the
    longest row's table beyond its `pos`, the pool's other blocks.  The
    result is the clean pools': no row reads as far as the wave's
    longest context, or its table."""
    args, fresh, kw = _wave("ragged", jnp.float32)
    q, kpool, vpool, tables, pos = args
    want = gqa_paged_decode(*args, 1, fresh, interpret=True, **kw)
    read = np.zeros(kpool.shape[1], bool)
    read[0] = True          # the null block: copied, under the mask
    for row, n in zip(np.asarray(tables), np.asarray(pos)):
        read[row[:-(-int(n) // BS)]] = True
    assert not read.all()
    # a row's last block holds clean slots past `pos` too: those are
    # masked, and a NaN under the mask would still reach the product
    # with V (0 x NaN); only whole blocks are poisoned
    poison = jnp.asarray(~read)[None, :, None, None]
    kpool, vpool = (jnp.where(poison, jnp.nan, p) for p in (kpool, vpool))
    got = gqa_paged_decode(q, kpool, vpool, tables, pos, 1, fresh,
                           interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # ... and the reference, which gathers every table whole, does read
    # them (masked scores, but NaN values in the weighted sum)
    assert np.isnan(np.asarray(gqa_paged_decode_reference(
        q, kpool, vpool, tables, pos, 1, fresh, **kw))).any()


# -- which programs take the kernel ------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    cfg = laguna_config("nano", dtype=jnp.float32)
    return cfg, laguna_init(jax.random.PRNGKey(0), cfg)


def _programs(cfg, params):
    paged = D.laguna_init_paged_cache(cfg, 3, num_blocks=17, block_size=BS)
    return {
        "paged_decode": (
            lambda c, t: D.laguna_decode_step(params, c, t, cfg),
            (paged, jnp.ones((3,), jnp.int32))),
        "dense_decode": (
            lambda c, t: D.laguna_decode_step(params, c, t, cfg),
            (D.laguna_init_cache(cfg, 3), jnp.ones((3,), jnp.int32))),
        "paged_prefill": (
            lambda c, t: D.laguna_paged_prefill(
                params, c, t, cfg, prefix_len=0, n_tail=20, slot=1,
                row_bt=jnp.zeros((cfg.max_seq // BS,), jnp.int32)),
            (paged, jnp.ones((1, 32), jnp.int32))),
    }


@pytest.mark.parametrize("backend,program,kernels", [
    ("cpu", "paged_decode", 0), ("tpu", "paged_decode", 2),
    ("tpu", "dense_decode", 0), ("tpu", "paged_prefill", 0)])
def test_only_the_paged_decode_step_on_the_chip_holds_the_kernel(
        tiny, monkeypatch, backend, program, kernels):
    """A paged cache, one column a row and the TPU backend take the
    kernel, one ``pallas_call`` a full layer (nano has two); the CPU,
    the dense cache and a prefill keep the ``jnp`` paths (the expert
    layers' own kernels, which every program on the chip holds, are
    tests/test_moe_dispatch.py's and tests/test_grouped_swiglu.py's)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    cfg, _ = tiny
    fn, args = _programs(*tiny)[program]
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _named(jaxpr, scopes.GQA_PAGED_DECODE) == kernels
    assert kernels in (0, len(cfg.layers_of("full")))
    others = sum(_named(jaxpr, k)
                 for k in (scopes.MOE_DISPATCH, scopes.MOE_COMBINE,
                           scopes.GROUPED_SWIGLU))
    assert _count(jaxpr, "pallas_call") == kernels + others


def test_on_the_kernels_path_no_view_is_gathered(tiny, monkeypatch):
    """The ``jnp`` path gathers every row's table to the
    dense-equivalent (rows, max_seq, kv_width) view; the kernel's path
    computes nothing of that size, nor a chunk's gathered view."""
    cfg, _ = tiny
    fn, args = _programs(*tiny)["paged_decode"]
    views = {(3, cfg.max_seq, cfg.kv_width),
             (3, cfg.max_seq // BS, BS, cfg.kv_width)}
    assert views & _shapes(jax.make_jaxpr(fn)(*args).jaxpr)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    fn, args = _programs(*tiny)["paged_decode"]    # a trace of its own
    assert not views & _shapes(jax.make_jaxpr(fn)(*args).jaxpr)


def _prefilled(cfg, params):
    """Two rows prefilled into the pool over tables out of order, one
    row idle between them."""
    cache = D.laguna_init_paged_cache(cfg, 3, num_blocks=20, block_size=BS)
    rng = np.random.RandomState(3)
    prompts = {}
    for slot, n in ((0, 21), (2, 40)):
        toks = np.zeros((1, 48), np.int32)
        toks[0, 48 - n:] = prompts[slot] = rng.randint(2, 500, n)
        row_bt = np.zeros((cfg.max_seq // BS,), np.int32)
        row_bt[:4] = 1 + 4 * slot + np.arange(4)[::-1]
        _, cache = jax.jit(functools.partial(
            D.laguna_paged_prefill, cfg=cfg, prefix_len=0, n_tail=n,
            slot=slot))(params, cache, jnp.asarray(toks),
                        row_bt=jnp.asarray(row_bt))
    return cache, prompts


def test_the_decode_step_through_the_kernel_is_the_jnp_step(tiny,
                                                            monkeypatch):
    """Prefill two rows into the pool, leave one idle, then decode steps
    by both paths: the same logits, the same cache."""
    cfg, params = tiny
    cache, _ = _prefilled(cfg, params)
    assert cache["pos"].tolist() == [21, 0, 40]
    tokens = jnp.asarray([5, 0, 7], jnp.int32)

    def two_steps():
        step = jax.jit(lambda c: D.laguna_decode_step(params, c, tokens,
                                                      cfg))
        logits, after = step(cache)
        logits2, after2 = step(after)
        return (logits, logits2), after2

    want_logits, want = two_steps()
    _steer(monkeypatch)
    got_logits, got = two_steps()
    live = np.asarray([0, 2])
    for g, w in zip(got_logits, want_logits):
        np.testing.assert_allclose(np.asarray(g)[live], np.asarray(w)[live],
                                   atol=F32_ATOL)
    assert got["pos"].tolist() == [23, 0, 42]
    for name in ("k", "v", "wk", "wv"):
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), atol=F32_ATOL)


def test_decoding_through_the_kernel_answers_as_the_dense_layout(
        tiny, interpreted):
    """Greedy decoding of a prefilled row through the kernel: the dense
    layout's logits step by step (`laguna_prefill`, then the dense
    decode step) and `laguna_generate`'s tokens."""
    cfg, params = tiny
    cache, prompts = _prefilled(cfg, params)
    prompt = jnp.asarray(prompts[2][None])
    new = 5
    want_tokens = np.asarray(jax.jit(lambda p, t: D.laguna_generate(
        p, t, cfg, max_new_tokens=new, temperature=0.0))(params, prompt))[0]
    logits, dense = jax.jit(lambda p, t: D.laguna_prefill(p, t, cfg))(
        params, prompt)
    step = jax.jit(lambda c, t: D.laguna_decode_step(params, c, t, cfg))
    got_tokens = [int(jnp.argmax(logits[0, :cfg.vocab_size]))]
    for _ in range(new - 1):
        tok = got_tokens[-1]
        want_logits, dense = step(dense, jnp.asarray([tok], jnp.int32))
        got_logits, cache = step(cache, jnp.asarray([0, 0, tok], jnp.int32))
        np.testing.assert_allclose(np.asarray(got_logits[2]),
                                   np.asarray(want_logits[0]),
                                   atol=10 * F32_ATOL)
        got_tokens.append(int(jnp.argmax(got_logits[2, :cfg.vocab_size])))
    assert got_tokens == want_tokens[-new:].tolist()
