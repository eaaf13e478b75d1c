"""The llama-lineage decoder in plain ``jax.numpy``: a fixture's copy of
the yardstick (see ../../README.txt).

Follows the published model (Touvron et al. 2023; Su et al. 2021 for
the rotary embedding): token embedding, pre-RMSNorm blocks without
biases, rotary position embedding on q and k, causal softmax attention
scaled by 1/sqrt(head_dim) with each K/V head shared by ``n_head /
n_kv_head`` query heads, SwiGLU (``down(silu(gate(x)) * up(x))``), final
RMSNorm, an untied output head.  float32 throughout under
``default_matmul_precision("highest")``; no kernel, cache, remat, scan or
batching trick, and nothing imported from ``ray_tpu.models``.

Departures, about layout and not mathematics: it reads the program's
parameter tree (layers stacked on a leading axis, ``wq`` as (d, heads,
head_dim)); the rotary pairs are (x_2i, x_2i+1) as in Su et al. and
Meta's code (Hugging Face's ``rotate_half`` pairs (x_i, x_i+hd/2): the
same map under a fixed permutation of the columns of ``wq`` and ``wk``,
which random weights cannot tell apart); it upcasts one layer at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _rmsnorm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _rope(x, theta):
    """x (B, T, H, hd): pair (2i, 2i+1) of position t turned by
    t * theta ** (-2i / hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=_F32) / hd)
    ang = jnp.arange(T, dtype=_F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _layer(x, p, theta, eps):
    """One block on x (B, T, d); p is one layer's float32 weights."""
    T = x.shape[1]
    h, hd = p["attn"]["wq"].shape[-2:]
    kv = p["attn"]["wk"].shape[-2]
    a = _rmsnorm(x, p["ln1"]["scale"], eps)
    q = _rope(jnp.einsum("btd,dhk->bthk", a, p["attn"]["wq"]), theta)
    k = _rope(jnp.einsum("btd,dhk->bthk", a, p["attn"]["wk"]), theta)
    v = jnp.einsum("btd,dhk->bthk", a, p["attn"]["wv"])
    # query head j reads K/V head j // (h / kv)
    k, v = (jnp.repeat(t, h // kv, axis=2) for t in (k, v))
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s,
                  -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, axis=-1), v)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, p["attn"]["wo"])
    m = _rmsnorm(x, p["ln2"]["scale"], eps)
    gate = jnp.einsum("btd,df->btf", m, p["mlp"]["w_gate"])
    up = jnp.einsum("btd,df->btf", m, p["mlp"]["w_up"])
    return x + jnp.einsum("btf,fd->btd", jax.nn.silu(gate) * up,
                          p["mlp"]["w_down"])


def logits(params, tokens, *, vocab_size: int, rope_theta: float,
           rms_eps: float):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32; the
    head's padded columns are left out."""
    with jax.default_matmul_precision("highest"):
        x = params["wte"].astype(_F32)[tokens]
        n_layer = params["blocks"]["ln1"]["scale"].shape[0]
        for i in range(n_layer):
            p = jax.tree.map(lambda a: a[i].astype(_F32),
                             params["blocks"])
            x = _layer(x, p, rope_theta, rms_eps)
        x = _rmsnorm(x, params["ln_f"]["scale"].astype(_F32), rms_eps)
        return jnp.einsum("btd,dv->btv", x,
                          params["lm_head"][:, :vocab_size].astype(_F32))


def loss(params, tokens, *, vocab_size: int, rope_theta: float,
         rms_eps: float):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size,
                rope_theta=rope_theta, rms_eps=rms_eps)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
