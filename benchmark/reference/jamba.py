"""Jamba forward and loss in plain ``jax.numpy``: the yardstick's copy.

Follows the published model (Lieber et al. 2024, "Jamba: A Hybrid
Transformer-Mamba Language Model"; Gu & Dao 2023, "Mamba"; Hugging Face
``modeling_jamba``).  ``h = E[tokens]``, no positions of any kind.  For
layer ``i``: ``h <- h + mixer_i(RMSNorm(h))``, then ``h <- h +
W_down(silu(W_gate m) * W_up m)`` with ``m = RMSNorm(h)``.  Logits
``RMSNorm(h) E^T`` through the tied embedding.

* attention (``i % attn_period == attn_offset``): ``q = W_q u``,
  ``k = W_k u``, ``v = W_v u`` with fewer K/V heads than query heads,
  causal softmax of ``q k^T / sqrt(head_dim)``, no rotation, no bias, no
  window; ``W_o``.
* Mamba (every other layer): ``[x, z] = W_in u``;
  ``x <- silu(conv1d_causal_depthwise(x) + bias)``;
  ``[dt, B, C] = split(W_x x)``, each through its own RMSNorm;
  ``D = softplus(W_dt dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(D_t A) * s_{t-1} + (D_t * x_t) (x) B_t``;
  ``y_t = s_t C_t + D_skip * x_t``; ``out = W_out(y * silu(z))``.

float32 throughout with ``precision="highest"``; the recurrence is a
``lax.scan`` over time steps, one token after another; no kernel, cache,
chunking, remat or batching trick, and nothing imported from
``ray_tpu.models``.

Departures, all about layout and not mathematics: it reads the program's
parameter tree (layers stacked by kind, ``params["mamba"]`` and
``params["attn"]``; ``A_log`` as (d_state, d_inner); ``conv_w`` as
(d_conv, d_inner)), the SSM state is carried as (d_state, d_inner), and
it upcasts one layer at a time, so that a 3 B-parameter model needs no
second copy.  What the parameter tree does not show (the norm's
epsilon, the layer pattern) is stated by the caller
(``families/jamba.py reference_kwargs``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale


def _mlp(x, p, eps):
    m = _rmsnorm(x, p["ln2"]["scale"], eps)
    gate = jnp.einsum("btd,df->btf", m, p["mlp"]["w_gate"], precision=_HI)
    up = jnp.einsum("btd,df->btf", m, p["mlp"]["w_up"], precision=_HI)
    return x + jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                          p["mlp"]["w_down"], precision=_HI)


def _attention(u, p):
    """u (B, T, d); K and V have n_kv_head heads, each serving
    n_head / n_kv_head query heads."""
    T = u.shape[1]
    h, hd = p["wq"].shape[1:]
    kv = p["wk"].shape[1]
    q = jnp.einsum("btd,dhk->bthk", u, p["wq"], precision=_HI)
    k = jnp.einsum("btd,dhk->bthk", u, p["wk"], precision=_HI)
    v = jnp.einsum("btd,dhk->bthk", u, p["wv"], precision=_HI)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=_HI) \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v, precision=_HI)
    return jnp.einsum("bqhk,hkd->bqd", o, p["wo"], precision=_HI)


def _mamba(u, p, eps):
    """u (B, T, d); every sequence starts from a zero state."""
    B, T, _ = u.shape
    K, di = p["conv_w"].shape
    N = p["A_log"].shape[0]
    R = p["dt_proj"].shape[0]
    xz = jnp.einsum("btd,de->bte", u, p["in_proj"], precision=_HI)
    x, z = xz[..., :di], xz[..., di:]
    # causal depthwise convolution: x[t-K+1 .. t], zeros before the start
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xp[:, k:k + T] * p["conv_w"][k] for k in range(K))
    x = jax.nn.silu(conv + p["conv_b"])
    dbc = jnp.einsum("bte,er->btr", x, p["x_proj"], precision=_HI)
    dt = _rmsnorm(dbc[..., :R], p["dt_norm"], eps)
    Bm = _rmsnorm(dbc[..., R:R + N], p["b_norm"], eps)
    Cm = _rmsnorm(dbc[..., R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(
        jnp.einsum("btr,re->bte", dt, p["dt_proj"], precision=_HI)
        + p["dt_bias"])
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
    return jnp.einsum("bte,ed->btd", y * jax.nn.silu(z), p["out_proj"],
                      precision=_HI)


def _one_layer(stack, index):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(
            a, index, axis=0, keepdims=False).astype(_F32), stack)


@functools.partial(jax.jit, static_argnames=("eps",))
def _mamba_layer(x, stack, index, eps):
    p = _one_layer(stack, index)
    x = x + _mamba(_rmsnorm(x, p["ln1"]["scale"], eps), p["mixer"], eps)
    return _mlp(x, p, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _attn_layer(x, stack, index, eps):
    p = _one_layer(stack, index)
    x = x + _attention(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"])
    return _mlp(x, p, eps)


@jax.jit
def _embed(wte, tokens):
    return wte.astype(_F32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, wte, eps):
    x = _rmsnorm(x, ln_f["scale"].astype(_F32), eps)
    return jnp.einsum("btd,vd->btv", x, wte.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, attn_period: int,
           attn_offset: int, eps: float = 1e-6):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32; the
    embedding's padded rows are left out.  Layer ``i`` is attention iff
    ``i % attn_period == attn_offset``."""
    n_mamba = params["mamba"]["ln1"]["scale"].shape[0]
    n_attn = params["attn"]["ln1"]["scale"].shape[0]
    x = _embed(params["wte"], tokens)
    m = a = 0
    for i in range(n_mamba + n_attn):
        if i % attn_period == attn_offset:
            x = _attn_layer(x, params["attn"], jnp.int32(a), eps)
            a += 1
        else:
            x = _mamba_layer(x, params["mamba"], jnp.int32(m), eps)
            m += 1
    assert (m, a) == (n_mamba, n_attn), "layer pattern and weights differ"
    return _head(x, params["ln_f"], params["wte"][:vocab_size], eps)


def loss(params, tokens, *, vocab_size: int, **stated):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size, **stated)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
