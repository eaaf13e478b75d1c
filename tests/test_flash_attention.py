"""Flash-attention kernel numerics vs the XLA reference oracle.

Runs the pallas kernels in interpreter mode on CPU (pallas_call
interpret=True) — real-TPU execution is covered by bench.py on the chip.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import reference_attention
from ray_tpu.ops.flash_attention import flash_attention


def _rand_qkv(key, B, T, H, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, H, D), dtype)
    k = jax.random.normal(kk, (B, T, H, D), dtype)
    v = jax.random.normal(kv, (B, T, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 128, 2, 64)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          interpret=True)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_flash_forward_uneven_blocks():
    # T not a multiple of the requested block → block shrink path
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 96, 1, 64)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True)
    want = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 64, 2, 32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                            interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [True, False])
def test_resident_kv_forward_matches_reference(causal):
    # whole-kv-resident kernel with the in-kernel causal-early-stop loop
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 2, 256, 2, 64)
    got = flash_attention(q, k, v, causal=causal, resident_kv=True,
                          interpret=True)
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_resident_kv_gradients_match_reference(causal):
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 256, 2, 32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, resident_kv=True,
                            interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_resident_kv_multi_chunk_gradients():
    # T large enough that bq=256/chunk=512 runs multiple loop trips with
    # a qi-dependent bound — exercises the dynamic-trip-count path.
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 1, 1024, 1, 32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, resident_kv=True,
                            interpret=True)
        return jnp.sum(o * o)

    def loss_ref(q, k, v):
        o = reference_attention(q, k, v, causal=True)
        return jnp.sum(o * o)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def test_flash_bf16_close_to_f32_reference():
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 1, 128, 2, 64, jnp.bfloat16)
    got = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          interpret=True).astype(jnp.float32)
    want = reference_attention(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-2,
                               rtol=5e-2)


# ---------------------------------------------------------------------------
# The triangle kernels: the default for causal T <= 2048 with no explicit
# blocks (a head whole in VMEM, only the tiles on or under the diagonal).
# ---------------------------------------------------------------------------

def _kernels_of(fn, *args):
    """The names of the pallas kernels a traced call holds."""
    from ray_tpu._private import scopes
    jaxpr = str(jax.make_jaxpr(fn)(*args))
    # longest first: "flash_dq" is no part of "flash_tri_dq", but keep
    # a name from matching inside a longer one all the same
    found, rest = set(), jaxpr
    for name in sorted(scopes.KERNELS, key=len, reverse=True):
        if name in rest:
            found.add(name)
            rest = rest.replace(name, "")
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("T", [256, 512, 1024, 384])
def test_default_causal_path_matches_reference(T, dtype):
    """Forward and gradients of the path auto dispatch takes, at D=64:
    the triangle kernels where T divides the tile, the classic kernels
    (same answer) at T=384, which does not."""
    from ray_tpu._private import scopes
    q, k, v = _rand_qkv(jax.random.PRNGKey(T), 1, T, 2, 64, dtype)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731

    def loss_flash(q, k, v):
        o = f32(flash_attention(q, k, v, causal=True, interpret=True))
        return jnp.sum(o * jnp.cos(o)), o

    def loss_ref(q, k, v):
        o = reference_attention(f32(q), f32(k), f32(v), causal=True)
        return jnp.sum(o * jnp.cos(o)), o

    names = _kernels_of(jax.grad(lambda *a: loss_flash(*a)[0],
                                 argnums=(0, 1, 2)), q, k, v)
    if T == 384:
        assert names == set(scopes.KERNELS[:3]), names
    else:
        assert names == set(scopes.KERNELS[6:8]), names

    (_, got), gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    (_, want), gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2),
                                       has_aux=True)(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    gtol = 1e-4 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(f32(a)), np.asarray(b),
                                   atol=gtol, rtol=gtol,
                                   err_msg=f"d{name}")


@pytest.mark.parametrize("t_fwd,t_bwd", [(128, 128), (128, 256), (256, 128),
                                         (512, 256)])
def test_triangle_kernels_any_tiling(t_fwd, t_bwd):
    """The forward's and the backward's tile are chosen apart; every
    pairing gives the reference's answer."""
    import importlib
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    B, T, H, D = 1, 512, 2, 64
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), B, T, H, D)
    to3 = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, D)  # noqa: E731

    def loss_flash(q, k, v):
        o = fa._flash_tri(to3(q), to3(k), to3(v), 0.125, t_fwd, t_bwd, True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = to3(reference_attention(q, k, v, causal=True))
        return jnp.sum(o * jnp.cos(o))

    lf, gf = jax.value_and_grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    lr, gr = jax.value_and_grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-5)
    for a, b, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("bq,bk,visited", [(128, 256, 6), (256, 128, 6),
                                           (128, 512, 4), (512, 512, 1)])
def test_causal_walk_with_tiles_that_are_not_square(bq, bk, visited):
    """The walk is what PERF.md counts the resident kernels' tiles with
    too (256 / 512: 6 of 8 at T=1024); a pair counts as crossed when it
    holds a key after one of its queries."""
    from ray_tpu.ops.flash_attention import causal_walk
    T = 512
    walk = causal_walk(T, bq, bk)
    assert len(walk) == visited
    for qi, ki, crossed in walk:
        first_q, last_q = qi * bq, qi * bq + bq - 1
        first_k, last_k = ki * bk, ki * bk + bk - 1
        assert first_k <= last_q                   # something to see
        assert crossed == (last_k > first_q)       # something to hide


@pytest.mark.parametrize("n", [1, 2, 4, 8, 16])
def test_causal_tiles_counts_the_triangle(n):
    from ray_tpu.ops.flash_attention import causal_tiles, causal_walk
    t = 128
    assert causal_tiles(n * t, t, t) == (n * (n + 1) // 2, n * n)
    walk = causal_walk(n * t, t, t)
    assert [c for qi, ki, c in walk if qi == ki] == [True] * n
    assert not any(c for qi, ki, c in walk if qi != ki)
    assert all(ki <= qi for qi, ki, _ in walk)


def test_causal_tiles_at_the_shapes_perf_md_quotes():
    from ray_tpu.ops.flash_attention import causal_tiles
    assert causal_tiles(1024, 256, 256) == (10, 16)
    assert causal_tiles(1024, 128, 128) == (36, 64)
    assert causal_tiles(1024, 512, 512) == (3, 4)      # the forward's
    assert causal_tiles(1024, 256, 512) == (6, 8)      # resident 256/512
    assert causal_tiles(1024, 1024, 1024) == (1, 1)    # the old default
    assert causal_tiles(1024, 256, 1024) == (4, 4)     # its backward


_CLASSIC, _RESIDENT, _TRIANGLE = "classic", "resident", "triangle"


@pytest.mark.parametrize("case,T,kw,want", [
    ("causal_1024", 1024, {}, _TRIANGLE),
    ("causal_2048", 2048, {}, _TRIANGLE),
    ("causal_256", 256, {}, _TRIANGLE),
    ("causal_4096", 4096, {}, _RESIDENT),
    ("causal_384_no_tile", 384, {}, _CLASSIC),
    ("non_causal", 1024, {"causal": False}, _CLASSIC),
    ("explicit_blocks", 1024, {"block_q": 256, "block_k": 256}, _CLASSIC),
    ("explicit_bwd_blocks", 1024, {"block_q_bwd": 256}, _CLASSIC),
    ("resident_off", 1024, {"resident_kv": False}, _CLASSIC),
    ("resident_on", 1024, {"resident_kv": True}, _RESIDENT),
    ("wide_f32_head", 2048, {"D": 256}, _CLASSIC),
], ids=lambda x: x if isinstance(x, str) and "_" in x else None)
def test_dispatch_by_what_the_call_can_see(case, T, kw, want):
    """Which kernels a call takes, read from their names in the jaxpr
    (nothing is compiled): causal, T, D and dtype decide, no option."""
    from ray_tpu._private import scopes
    kw = dict(kw)
    D = kw.pop("D", 64)
    x = jnp.ones((1, T, 1, D), jnp.float32)

    def grad(q, k, v):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, **kw).sum(), argnums=(0, 1, 2))(q, k, v)

    names = _kernels_of(grad, x, x, x)
    families = {_CLASSIC: set(scopes.KERNELS[:3]),
                _RESIDENT: set(scopes.KERNELS[3:6]),
                _TRIANGLE: set(scopes.KERNELS[6:8])}
    assert names and names <= families[want], (case, names)


@pytest.mark.parametrize("mode,want", [("off", _CLASSIC), ("on", _RESIDENT),
                                       ("auto", _TRIANGLE)])
def test_flash_resident_knob_keeps_its_meaning(mode, want, monkeypatch):
    """`flash_resident` through causal_attention: "on" and "off" mean
    what they meant; only what "auto" resolves to changed."""
    from ray_tpu._private import scopes
    from ray_tpu.ops.attention import causal_attention
    monkeypatch.delenv("RAYTPU_FLASH_RESIDENT", raising=False)
    x = jnp.ones((1, 1024, 1, 64), jnp.bfloat16)
    names = _kernels_of(lambda q, k, v: causal_attention(
        q, k, v, use_flash=True, resident=mode), x, x, x)
    first = {_CLASSIC: scopes.FLASH_FWD, _RESIDENT: scopes.FLASH_RES_FWD,
             _TRIANGLE: scopes.FLASH_TRI_FWD}[want]
    assert first in names and names <= set(scopes.KERNELS), names
