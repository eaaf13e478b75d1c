"""Phi-4-mini-flash forward and loss in plain ``jax.numpy``: the
yardstick's copy.

Follows the published architecture (SambaY: Ren et al. 2025,
arXiv:2507.06607; its cross-decoder is YOCO's, Sun et al. 2024,
arXiv:2405.05254; its attention is Differential Attention, Ye et al.
2024, arXiv:2410.05258; its Mamba layers are Mamba-1, Gu & Dao 2023).
``h = E[tokens]``, no positions of any kind.  For layer ``i`` of ``L``,
``half = L / 2``: ``h <- h + mixer_i(LN(h))``, then ``h <- h + (silu(g)
* a) W_2`` with ``[g, a] = LN(h) W_1``; ``LN`` is LayerNorm with weight
and bias.  Logits ``LN_f(h) E^T`` through the tied embedding.  EVERY
layer runs over EVERY position: nothing here knows that a served
prompt's cross-decoder needs one.

* Mamba (``i`` even, ``i <= half``): ``[x, z] = u W_in``; ``x <-
  silu(conv1d_causal_depthwise(x) + bias)``; ``[dt, B, C] = split(x
  W_x)``, unnormed; ``dt = softplus(dt W_dt + b_dt)``; ``A =
  -exp(A_log)``; ``s_t = exp(dt_t A) * s_{t-1} + (dt_t * x_t) (x) B_t``;
  ``y_t = s_t C_t + D * x_t``; ``out = (y * silu(z)) W_out``.  Layer
  ``half``'s ``y`` is the memory ``m``.
* differential attention (``i`` odd, ``i <= half + 1``; window layers
  ``i < half`` see ``t - window < j <= t``, layer ``half + 1`` sees ``j
  <= t``): ``q = u W_q + b_q`` in ``n_head`` heads, ``[k, v] = u W_kv +
  b_kv`` in ``n_kv_head`` heads each.  Query heads (2p, 2p+1) are the
  pair ``(q1_p, q2_p)``, K heads (2r, 2r+1) the pair ``(k1_r, k2_r)``,
  ``v_r = [v_2r ; v_2r+1]``; pair p reads K/V pair ``p // (n_head /
  n_kv_head)``.  FOUR softmaxes a K/V pair at the published sizes (two
  query pairs, two halves each): ``a^s_p = softmax_j(q^s_p . k^s_r(j) /
  sqrt(hd))``, ``o^s_p = sum_j a^s_p(j) v_r(j)``; ``lam = exp(lq1 .
  lk1) - exp(lq2 . lk2) + lam_init(i)``, ``lam_init(i) = 0.8 - 0.6
  exp(-0.3 i)``; ``o_p = (1 - lam_init(i)) RMSNorm(o^1_p - lam
  o^2_p)``; ``out = concat_p(o_p) W_o + b_o``.
* Gated Memory Unit (``i`` even, ``i > half``): ``out = (silu(u W_1) *
  m) W_2``.
* cross-attention (``i`` odd, ``i > half + 1``): the layer's own ``q``
  against layer ``half + 1``'s ``k`` and ``v``, ``j <= t``; the same
  differential combine with this layer's own lambdas and norm.

float32 throughout with ``precision="highest"``; the recurrence is a
``lax.scan`` over time steps, one token after another; no kernel, no
cache, no padded query, no pair-head; nothing imported from
``ray_tpu.models``.

Departures, all about layout and memory and not mathematics: it reads
the program's parameter tree (``params["self"]`` the (Mamba, window)
pairs stacked, ``params["memory"]``, ``params["full"]``,
``params["cross"]`` the (GMU, cross) pairs stacked; ``A_log`` as
(d_state, d_inner); ``conv_w`` as (d_conv, d_inner); ``W_1`` fused);
attention runs in blocks of queries, the MLP in blocks of positions,
the head in blocks of positions and of the vocabulary whose logits are
gathered on the host, and
weights are upcast a layer at a time inside that layer's program (and
the host waits for each pair of layers), so that ``logits(params,
tokens[1, 4864])`` at the published widths fits beside a serving
engine.  What the parameter tree does not show (the head counts, the
window, the norms' epsilon) is stated by the caller
(``families/phi4flash.py reference_kwargs``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
#: queries attended at once; positions and vocabulary rows through the
#: head at once
_Q_BLOCK = 128
_HEAD_BLOCK = 256
_VOCAB_BLOCK = 32_768


def _layernorm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _mm(x, w):
    return jnp.einsum("...a,ab->...b", x, w, precision=_HI)


def _mlp(x, p, eps):
    """``x + MLP(LN(x))``, a block of positions at a time: a row's MLP
    is its own, and 4,864 rows of 20,480 in float32 are 0.4 GB twice."""
    def rows(xb):
        ga = _mm(_layernorm(xb, p["ln2"], eps), p["mlp"]["w1"])
        f = ga.shape[-1] // 2
        return xb + _mm(jax.nn.silu(ga[..., :f]) * ga[..., f:],
                        p["mlp"]["w2"])

    B, T, d = x.shape
    if T % _HEAD_BLOCK:
        return rows(x)
    blocks = x.reshape(B, T // _HEAD_BLOCK, _HEAD_BLOCK, d)
    return jnp.moveaxis(lax.map(rows, jnp.moveaxis(blocks, 1, 0)), 0,
                        1).reshape(B, T, d)


def _mamba(u, p):
    """u (B, T, d) -> (out (B, T, d), y (B, T, d_inner) before the
    gate); every sequence starts from a zero state."""
    B, T, _ = u.shape
    K, di = p["conv_w"].shape
    N = p["A_log"].shape[0]
    R = p["dt_proj"].shape[0]
    xz = _mm(u, p["in_proj"])
    x, z = xz[..., :di], xz[..., di:]
    # causal depthwise convolution: x[t-K+1 .. t], zeros before the start
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + T] * p["conv_w"][k] for k in range(K))
    x = jax.nn.silu(conv + p["conv_b"])
    dbc = _mm(x, p["x_proj"])
    dt = jax.nn.softplus(_mm(dbc[..., :R], p["dt_proj"]) + p["dt_bias"])
    Bm, Cm = dbc[..., R:R + N], dbc[..., R + N:]
    A = -jnp.exp(p["A_log"])                            # (N, di)

    def step(s, xs):
        dt_t, x_t, b_t, c_t = xs                        # (B, di) / (B, N)
        s = jnp.exp(dt_t[:, None, :] * A) * s \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return s, jnp.sum(s * c_t[:, :, None], axis=1)

    time_major = lambda a: jnp.swapaxes(a, 0, 1)  # noqa: E731
    _, y = lax.scan(step, jnp.zeros((B, N, di), _F32),
                    (time_major(dt), time_major(x), time_major(Bm),
                     time_major(Cm)))
    y = time_major(y) + p["D"] * x
    return _mm(y * jax.nn.silu(z), p["out_proj"]), y


def _queries(u, p, n_head):
    q = _mm(u, p["wq"]) + p["bq"]
    return q.reshape(*q.shape[:-1], n_head, -1)


def _keys_values(u, p, n_kv_head):
    kv = _mm(u, p["wkv"]) + p["bkv"]
    w = kv.shape[-1] // 2
    shape = (*kv.shape[:-1], n_kv_head, -1)
    return kv[..., :w].reshape(shape), kv[..., w:].reshape(shape)


def _differential(q, k, v, p, lam_init, window, eps):
    """q (B, T, n_head, hd) over k, v (B, T, n_kv_head, hd) of the same
    positions, ``j <= t`` and, with a `window`, ``j > t - window``:
    (B, T, d) after the combine, the norm and ``W_o``."""
    B, T, H, hd = q.shape
    R = k.shape[2] // 2                 # K/V pairs
    G = H // 2 // R                     # query pairs a K/V pair
    qp = q.reshape(B, T, R, G, 2, hd)
    kp = k.reshape(B, T, R, 2, hd)
    vp = v.reshape(B, T, R, 2 * hd)
    qb = _Q_BLOCK if T % _Q_BLOCK == 0 else T

    def block(i):
        at = (i * qb + jnp.arange(qb))[:, None]
        key = jnp.arange(T)[None, :]
        mask = key <= at
        if window is not None:
            mask = mask & (key > at - window)
        qi = lax.dynamic_slice_in_dim(qp, i * qb, qb, axis=1)
        # softmax s of pair (r, g): q^s . k^s over the allowed keys
        s = jnp.einsum("bqrgsd,bkrsd->brgsqk", qi, kp, precision=_HI) \
            / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("brgsqk,bkrd->bqrgsd", a, vp, precision=_HI)

    o = lax.map(block, jnp.arange(T // qb))     # (nq, B, qb, R, G, 2, 2hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, R * G, 2, 2 * hd)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam_init
    mixed = o[..., 0, :] - lam * o[..., 1, :]
    mixed = mixed * lax.rsqrt(jnp.mean(jnp.square(mixed), axis=-1,
                                       keepdims=True) + eps) * p["subln"]
    mixed = (1.0 - lam_init) * mixed
    return _mm(mixed.reshape(B, T, -1), p["wo"]) + p["bo"]


def _up(tree):
    return jax.tree.map(lambda a: a.astype(_F32), tree)


def _lam_init(i):
    return 0.8 - 0.6 * jnp.exp(-0.3 * i.astype(_F32))


@functools.partial(jax.jit, static_argnames=("eps",))
def _mamba_layer(x, p, eps):
    p = _up(p)
    out, y = _mamba(_layernorm(x, p["ln1"], eps), p["mixer"])
    return _mlp(x + out, p, eps), y


@functools.partial(jax.jit, static_argnames=(
    "eps", "n_head", "n_kv_head", "window"))
def _self_attn_layer(x, p, i, eps, n_head, n_kv_head, window):
    p = _up(p)
    u = _layernorm(x, p["ln1"], eps)
    k, v = _keys_values(u, p["attn"], n_kv_head)
    x = x + _differential(_queries(u, p["attn"], n_head), k, v, p["attn"],
                          _lam_init(i), window, eps)
    return _mlp(x, p, eps), k, v


@functools.partial(jax.jit, static_argnames=("eps",))
def _gmu_layer(x, p, m, eps):
    p = _up(p)
    u = _layernorm(x, p["ln1"], eps)
    out = _mm(jax.nn.silu(_mm(u, p["gmu"]["w_in"])) * m, p["gmu"]["w_out"])
    return _mlp(x + out, p, eps)


@functools.partial(jax.jit, static_argnames=("eps", "n_head"))
def _cross_layer(x, p, i, k, v, eps, n_head):
    p = _up(p)
    u = _layernorm(x, p["ln1"], eps)
    x = x + _differential(_queries(u, p["attn"], n_head), k, v, p["attn"],
                          _lam_init(i), None, eps)
    return _mlp(x, p, eps)


@jax.jit
def _pick(stack, index):
    """One layer out of its stack, as stored: the layer's program
    upcasts it, so no float32 copy of a layer outlives its layer."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, index, axis=0,
                                           keepdims=False), stack)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, rows, eps):
    return jnp.einsum("btd,vd->btv", _layernorm(x, _up(ln_f), eps),
                      rows.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, n_head: int,
           n_kv_head: int, window: int, eps: float = 1e-5):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32, a host
    array; the embedding's padded rows are left out.  The layers'
    kinds follow from their place (module docstring) and the tree's
    stacks."""
    n_self = params["self"]["mamba"]["ln1"]["scale"].shape[0]
    n_cross = params["cross"]["gmu"]["ln1"]["scale"].shape[0]
    half = 2 * n_self
    assert 2 * (n_self + 1 + n_cross) == 2 * half, \
        "the stacks are not a self-decoder and a cross-decoder"
    how = dict(eps=float(eps), n_head=int(n_head))
    x = _embed(params["wte"], tokens)
    for j in range(n_self):                       # layers 2j, 2j + 1
        x, _ = _mamba_layer(x, _pick(params["self"]["mamba"], j),
                            float(eps))
        x, _, _ = _self_attn_layer(
            x, _pick(params["self"]["window"], j), jnp.int32(2 * j + 1),
            n_kv_head=int(n_kv_head), window=int(window), **how)
        # one pair at a time on the device: a host that runs ahead has
        # every queued layer's buffers allocated at once
        jax.block_until_ready(x)
    x, m = _mamba_layer(x, params["memory"], float(eps))
    x, k, v = _self_attn_layer(x, params["full"], jnp.int32(half + 1),
                               n_kv_head=int(n_kv_head), window=None, **how)
    for j in range(n_cross):                      # layers half + 2 + 2j, ..
        x = _gmu_layer(x, _pick(params["cross"]["gmu"], j), m, float(eps))
        x = _cross_layer(x, _pick(params["cross"]["attn"], j),
                         jnp.int32(half + 3 + 2 * j), k, v, **how)
        jax.block_until_ready(x)
    # the head a block of positions and of the vocabulary at a time,
    # the logits gathered on the host: (1, 4864, 200064) float32 is
    # 3.9 GB and the embedding upcast 2 GB, which no chip that holds
    # the serving engine has room for
    blocks = []
    for r in range(0, vocab_size, _VOCAB_BLOCK):
        rows = params["wte"][r:min(r + _VOCAB_BLOCK, vocab_size)]
        blocks.append(np.concatenate([
            np.asarray(_head(x[:, i:i + _HEAD_BLOCK], params["ln_f"], rows,
                             float(eps)))
            for i in range(0, x.shape[1], _HEAD_BLOCK)], axis=1))
    return np.concatenate(blocks, axis=-1)


def loss(params, tokens, *, vocab_size: int, **stated):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size, **stated)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
