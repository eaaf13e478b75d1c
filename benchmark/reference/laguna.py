"""Laguna forward and loss in plain ``jax.numpy``: the yardstick's copy.

Follows poolside's published ``config.json`` (``model_type: laguna``,
Laguna-XS.2).  ``h = E[tokens]``; for layer ``l``: ``h <- h +
Attn_l(RMSNorm(h))``, ``h <- h + FFN_l(RMSNorm(h))``; logits
``RMSNorm(h) W_head^T`` (untied); no bias anywhere.

* Attention.  ``q = u W_q`` (``H_l`` heads of ``head_dim``: the count
  is the layer's own, ``num_attention_heads_per_layer``), ``k = u W_k``,
  ``v = u W_v`` (``n_kv_head`` heads); query head ``h`` reads K/V head
  ``h // (H_l / n_kv_head)``.  A ``full`` layer rotates the first
  ``full_rotary_dim`` dims of each head (``partial_rotary_factor``) with
  YaRN's frequencies, cos and sin times ``attention_factor``; a
  ``window`` layer rotates the whole head at its own base, unscaled.
  ``score = q.k / sqrt(head_dim)`` under an explicit (T, T) mask:
  ``j <= i`` (full) or ``i - window < j <= i`` (window).  ``g =
  sigmoid(u W_g)``, one scalar a head a token, multiplies the head's
  output before ``W_o``.
* FFN: a layer with ``mlp`` weights: ``W_down(silu(W_gate m) * W_up
  m)``; one with ``moe`` weights: ``p = softmax(m W_r)`` over all
  experts, chosen = top-k of ``p``, ``w = p[chosen] / sum * route_scale``,
  ``y = sum_{e in chosen} w_e Expert_e(m) + Shared(m)``, the weight on
  the expert's OUTPUT.

float32 throughout with ``precision="highest"``; no kernel, cache, sort
or grouped matmul: every expert is applied DENSELY to every token and
weighted by a mask; nothing imported from ``ray_tpu.models``.

What the config leaves open, and what is taken here as in the program
(``benchmark/configs/laguna-xs2.json`` ``assumed`` gives the reasons):
``gating: true`` is read per head, on the attention's output, from the
normed input; the router is softmax, top-k, renormalise, times
``moe_routed_scaling_factor``, with no selection bias; the shared
expert is summed ungated; no q/k norm.

Departures, all about layout and memory and not mathematics: it reads
the program's parameter tree (a list of layers; K and V projections
folded ``(d, n_kv_head * head_dim)``; the experts stacked); rotary pairs
are ``(2i, 2i+1)`` (a fixed column permutation of ``W_q`` / ``W_k`` from
the source's half-split layout: the same model under seeded weights);
attention runs in blocks of queries, each expert in turn over all
tokens, the head in blocks of positions whose logits are gathered on
the host, and weights are upcast a matrix at a time, so that
``logits(params, tokens[1, 8704])`` at the published widths fits beside
a serving engine.  What the parameter tree does not show is stated by
the caller (``families/laguna.py reference_kwargs``).
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
#: queries attended at once, positions through the head at once
_Q_BLOCK = 128
_HEAD_BLOCK = 1024


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale.astype(_F32)


def _mm(x, w):
    """x (..., a) @ w (a, b), the weight upcast here, one at a time."""
    return jnp.einsum("...a,ab->...b", x, w.astype(_F32), precision=_HI)


def _blocks(n: int, size: int) -> int:
    return size if n % size == 0 else n


def _yarn_inv_freq(dim, theta, factor, orig_max, beta_fast, beta_slow):
    """Hugging Face ``_compute_yarn_parameters`` over `dim` rotated
    dims: the frequencies between the correction range's ends pass from
    kept to divided by `factor`."""
    f = theta ** (-jnp.arange(0, dim, 2, dtype=_F32) / dim)
    if factor <= 1:
        return f

    def pair_of(rotations):
        return dim * math.log(orig_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=_F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)


def _rope(x, cos, sin):
    """x (B, T, H, r) with cos, sin (T, r / 2): pairs (2i, 2i+1)
    rotate."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[:, None], sin[:, None]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _attention(u, p, kind, n):
    """u (B, T, d) -> (B, T, d): one layer's attention of `kind`
    ("full" or "window") under its explicit mask; `n` the stated
    numbers."""
    B, T, _ = u.shape
    kv, hd = n["n_kv_head"], n["head_dim"]
    q = jnp.einsum("btd,dhk->bthk", u, p["wq"].astype(_F32), precision=_HI)
    k = _mm(u, p["wk"]).reshape(B, T, kv, hd)
    v = _mm(u, p["wv"]).reshape(B, T, kv, hd)
    pos = jnp.arange(T, dtype=_F32)[:, None]
    if kind == "full":
        r = n["full_rotary_dim"]
        ang = pos * _yarn_inv_freq(r, n["full_rope_theta"],
                                   n["rope_factor"], n["rope_orig_max"],
                                   n["beta_fast"], n["beta_slow"])
        cos = jnp.cos(ang) * n["attention_factor"]
        sin = jnp.sin(ang) * n["attention_factor"]
    else:
        r = hd
        ang = pos * n["window_rope_theta"] ** (
            -jnp.arange(0, hd, 2, dtype=_F32) / hd)
        cos, sin = jnp.cos(ang), jnp.sin(ang)
    q = jnp.concatenate([_rope(q[..., :r], cos, sin), q[..., r:]], axis=-1)
    k = jnp.concatenate([_rope(k[..., :r], cos, sin), k[..., r:]], axis=-1)
    H = q.shape[2]
    qg = q.reshape(B, T, kv, H // kv, hd)
    qb = _blocks(T, _Q_BLOCK)

    def queries(i):
        at = (i * qb + jnp.arange(qb))[:, None]
        key = jnp.arange(T)[None, :]
        mask = key <= at
        if kind == "window":
            mask = mask & (key > at - n["window"])
        qi = lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=_HI) \
            / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", w, v, precision=_HI)

    o = lax.map(queries, jnp.arange(T // qb))    # (nq, B, qb, kv, G, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H, hd)
    gate = jax.nn.sigmoid(_mm(u, p["wg"]))                    # (B, T, H)
    o = (o * gate[..., None]).reshape(B, T, H * hd)
    return _mm(o, p["wo"].reshape(H * hd, -1))


def _swiglu(m, p):
    return _mm(jax.nn.silu(_mm(m, p["w_gate"])) * _mm(m, p["w_up"]),
               p["w_down"])


def _experts(m, p, top_k, norm_topk, route_scale):
    """m (N, d): every expert applied to every token, one expert at a
    time, and weighted by whether the token chose it; plus the shared
    expert."""
    probs = jax.nn.softmax(_mm(m, p["router"]["w"]), axis=-1)
    w, chosen = lax.top_k(probs, top_k)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * route_scale
    y = _swiglu(m, p["shared"]) if "shared" in p else jnp.zeros_like(m)

    def one(e, y):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)   # (N,)
        weights = {k: lax.dynamic_index_in_dim(v, e, 0, keepdims=False)
                   for k, v in p["experts"].items()}
        return y + mine[:, None] * _swiglu(m, weights)

    return lax.fori_loop(0, p["router"]["w"].shape[1], one, y)


@functools.partial(jax.jit, static_argnames=("kind", "numbers"))
def _attn_half(x, p, kind, numbers):
    n = dict(numbers)
    return x + _attention(_rmsnorm(x, p["ln1"]["scale"], n["eps"]),
                          p["attn"], kind, n)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_half(x, p, eps):
    return x + _swiglu(_rmsnorm(x, p["ln2"]["scale"], eps), p["mlp"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "top_k", "norm_topk", "route_scale"))
def _expert_half(x, p, eps, top_k, norm_topk, route_scale):
    B, T, d = x.shape
    m = _rmsnorm(x, p["ln2"]["scale"], eps).reshape(B * T, d)
    return x + _experts(m, p["moe"], top_k, norm_topk,
                        route_scale).reshape(B, T, d)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, head, eps):
    return jnp.einsum("btd,vd->btv", _rmsnorm(x, ln_f["scale"], eps),
                      head.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, layer_types, n_kv_head: int,
           head_dim: int, window: int, top_k: int, full_rotary_dim: int,
           full_rope_theta: float, rope_factor: float, rope_orig_max: int,
           beta_fast: float, beta_slow: float, attention_factor: float,
           window_rope_theta: float, norm_topk: bool = True,
           route_scale: float = 1.0, eps: float = 1e-6):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32, a host
    array; the head's padded rows are left out.  `layer_types` names
    each layer of ``params["layers"]`` "full" or "window"; a layer's
    query heads and its FFN are read off its weights."""
    numbers = tuple(sorted(dict(
        n_kv_head=int(n_kv_head), head_dim=int(head_dim),
        window=int(window), full_rotary_dim=int(full_rotary_dim),
        full_rope_theta=float(full_rope_theta),
        rope_factor=float(rope_factor), rope_orig_max=int(rope_orig_max),
        beta_fast=float(beta_fast), beta_slow=float(beta_slow),
        attention_factor=float(attention_factor),
        window_rope_theta=float(window_rope_theta),
        eps=float(eps)).items()))
    x = _embed(params["wte"], tokens)
    for p, kind in zip(params["layers"], layer_types):
        x = _attn_half(x, p, str(kind), numbers)
        if "moe" in p:
            x = _expert_half(x, p, eps, int(top_k), bool(norm_topk),
                             float(route_scale))
        else:
            x = _dense_half(x, p, eps)
    # the head a block of positions at a time, the logits gathered on
    # the host: (1, 8704, 100352) float32 is 3.5 GB, which no chip that
    # holds the serving engine has room for
    head = params["head"][:vocab_size]
    return np.concatenate([
        np.asarray(_head(x[:, i:i + _HEAD_BLOCK], params["ln_f"], head, eps))
        for i in range(0, x.shape[1], _HEAD_BLOCK)], axis=1)


def loss(params, tokens, *, vocab_size: int, **stated):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size, **stated)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
