"""GPT-2 forward and loss in plain ``jax.numpy``: the yardstick's copy.

Follows the published model (Radford et al. 2019; Hugging Face
``modeling_gpt2``): learned positions, pre-LayerNorm blocks, fused qkv
projection, causal softmax attention scaled by 1/sqrt(head_dim),
``gelu_new`` (tanh approximation), final LayerNorm, logits through the
tied embedding.  float32 throughout with ``precision="highest"`` (on a
TPU a float32 matmul otherwise runs in bf16 passes); no kernel, cache,
remat, scan or batching trick, and nothing imported from
``ray_tpu.models``.

Departures, both about layout and not mathematics: it reads the
program's parameter tree (layers stacked on a leading axis, ``qkv_w`` as
(d, 3, heads, head_dim)), and it upcasts one layer at a time, so that a
1.5 B-parameter model needs no second copy.  Dropout is absent, as in
the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _layer(x, p, eps):
    """One block on x (B, T, d); p is one layer's float32 weights."""
    T = x.shape[1]
    hd = p["attn"]["qkv_w"].shape[-1]
    a = _layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"], eps)
    qkv = jnp.einsum("btd,dchk->btchk", a, p["attn"]["qkv_w"],
                     precision=_HI) + p["attn"]["qkv_b"]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhk,bshk->bhqs", q, k, precision=_HI) \
        / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqs,bshk->bqhk", w, v, precision=_HI)
    x = x + jnp.einsum("bqhk,hkd->bqd", o, p["attn"]["o_w"],
                       precision=_HI) + p["attn"]["o_b"]
    m = _layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"], eps)
    h = _gelu_new(jnp.einsum("btd,df->btf", m, p["mlp"]["fc_w"],
                             precision=_HI) + p["mlp"]["fc_b"])
    return x + jnp.einsum("btf,fd->btd", h, p["mlp"]["proj_w"],
                          precision=_HI) + p["mlp"]["proj_b"]


@jax.jit
def _embed(wte, wpe, tokens):
    T = tokens.shape[1]
    return wte.astype(_F32)[tokens] + wpe.astype(_F32)[:T]


@jax.jit
def _apply_layer(x, blocks, index, eps):
    p = jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(
            a, index, axis=0, keepdims=False).astype(_F32), blocks)
    return _layer(x, p, eps)


@jax.jit
def _head(x, ln_f, wte, eps):
    x = _layernorm(x, ln_f["scale"].astype(_F32),
                   ln_f["bias"].astype(_F32), eps)
    return jnp.einsum("btd,vd->btv", x, wte.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, eps: float = 1e-5):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32; the
    embedding's padded rows are left out."""
    x = _embed(params["wte"], params["wpe"], tokens)
    n_layer = params["blocks"]["ln1"]["scale"].shape[0]
    for i in range(n_layer):
        x = _apply_layer(x, params["blocks"], jnp.int32(i), eps)
    return _head(x, params["ln_f"], params["wte"][:vocab_size], eps)


def loss(params, tokens, *, vocab_size: int, eps: float = 1e-5):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size, eps=eps)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
