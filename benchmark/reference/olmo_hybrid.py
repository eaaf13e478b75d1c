"""Olmo-Hybrid forward and loss in plain ``jax.numpy``: the yardstick's
copy.

Follows Ai2's published ``config.json`` (``model_type: olmo_hybrid``,
Olmo-Hybrid-7B) and, for the linear-attention layers, Gated DeltaNet
(arXiv 2412.06464), which the ``linear_*`` keys name.  ``h =
E[tokens]``; for layer ``l``: ``h <- h + RMSNorm(Mixer_l(h))``, ``h <-
h + RMSNorm(MLP_l(h))``: the Olmo family's block (Olmo 2, arXiv
2501.00656), mixer and MLP reading ``h`` itself and the norms on their
OUTPUTS; logits ``RMSNorm(h) W_head^T`` (untied); no bias and no
positions anywhere (``rope_theta: null`` read as no rotary).

* A ``full_attention`` layer.  ``q = RMSNorm(h W_q)``, ``k = RMSNorm(h
  W_k)``, each norm over the WHOLE projection with a learned weight of
  its width, before the heads are cut; ``v = h W_v``; ``H`` heads of
  ``head_dim`` over ``n_kv_head`` K/V heads (as many, for the published
  model), ``score = q.k / sqrt(head_dim)`` under an explicit mask ``j
  <= i``, softmax; ``out = concat_h(o_h) W_o``.
* A ``linear_attention`` layer, per head with keys of ``dk`` and values
  of ``dv``: ``q~ = SiLU(conv(h W_q))``, ``k~ = SiLU(conv(h W_k))``,
  ``v = SiLU(conv(h W_v))``, THREE causal depthwise convolutions of the
  kernel the weights have, from zeros; ``q = q~ / |q~| * dk^-1/2``, ``k
  = k~ / |k~|`` (eps 1e-6); ``g = -exp(A_log_h) * softplus(h W_a +
  dt_bias_h)``, ONE log-decay a head a token; ``beta = sigmoid(h
  W_b)``, doubled where ``neg_eigval``.  The state ``S`` (dk, dv)
  starts at zero and takes the tokens ONE AT A TIME, in a scan over
  time: ``S' = exp(g_t) S``; ``S = S' + beta_t k_t (v_t - S'^T
  k_t)^T``; ``o_t = S^T q_t``.  No chunk, no triangular solve: the
  sequential recurrence is the definition the program's chunked form is
  held to.  ``out = concat_h(RMSNorm_h(o) * SiLU((h W_g)_h)) W_o``.
* The MLP, every layer: ``(SiLU(x W_gate) * (x W_up)) W_down``.

float32 throughout with ``precision="highest"``; no kernel, cache or
chunk; nothing imported from ``ray_tpu.models``.

What the config leaves open, and what is taken here as in the program
(``benchmark/configs/olmo-hybrid-7b.json`` ``assumed`` gives the
reasons): the output norms and the QK-norm over the whole projection
are the Olmo family's; ``rope_theta: null`` is no rotary; the SiLU
output gate, the head norm and the doubled ``beta`` are Gated
DeltaNet's as flash-linear-attention configures it under these keys.

Departures, all about layout and memory and not mathematics: it reads
the program's parameter tree (a list of layers; K and V projections
folded ``(d, n_kv_head * head_dim)``; a linear layer's projections
``(d, H, dk | dv)`` and taps ``(K, H, dk | dv)``); attention runs in
blocks of queries, the head in blocks of positions whose logits are
gathered on the host, and weights are upcast a matrix at a time, so
that ``logits(params, tokens[1, 6656])`` at the published widths fits
beside a serving engine.  What the parameter tree does not show is
stated by the caller (``families/olmo_hybrid.py reference_kwargs``).
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
    """x (..., a) @ w (a, ...), the weight upcast here, one at a time,
    its trailing axes folded."""
    return jnp.einsum("...a,ab->...b", x,
                      w.astype(_F32).reshape(w.shape[0], -1), precision=_HI)


def _attention(h, p, n_kv_head, head_dim, eps):
    """h (B, T, d) -> (B, T, d): one full layer before its output norm,
    causal."""
    B, T, _ = h.shape
    kv, hd = n_kv_head, head_dim
    q = _rmsnorm(_mm(h, p["wq"]), p["q_norm"], eps)
    H = q.shape[-1] // hd
    k = _rmsnorm(_mm(h, p["wk"]), p["k_norm"], eps).reshape(B, T, kv, hd)
    v = _mm(h, p["wv"]).reshape(B, T, kv, hd)
    qg = q.reshape(B, T, kv, H // kv, hd)
    qb = _Q_BLOCK if T % _Q_BLOCK == 0 else T

    def queries(i):
        at = (i * qb + jnp.arange(qb))[:, None]
        mask = jnp.arange(T)[None, :] <= at
        qi = lax.dynamic_slice_in_dim(qg, i * qb, qb, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=_HI) \
            / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(mask[None, None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bkgqs,bskd->bqkgd", w, v, precision=_HI)

    o = lax.map(queries, jnp.arange(T // qb))    # (nq, B, qb, kv, G, hd)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H * hd)
    return _mm(o, p["wo"].reshape(H * hd, -1))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv_silu(x, taps):
    """x (B, T, H, c) through a causal depthwise convolution with taps
    (K, H, c), from zeros, then SiLU: ``y_t = sum_i w_i x_{t - (K-1) +
    i}``, the last tap meets the token itself."""
    K, T = taps.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0)))
    w = taps.astype(_F32)
    return jax.nn.silu(sum(xp[:, i:i + T] * w[i] for i in range(K)))


def _deltanet(h, p, neg_eigval, eps):
    """h (B, T, d) -> (B, T, d): one Gated DeltaNet layer before its
    output norm, from a zero state, its recurrence one token at a
    time."""
    B, T, _ = h.shape
    _, H, dk = p["conv_q"].shape
    dv = p["conv_v"].shape[-1]
    q = _conv_silu(_mm(h, p["wq"]).reshape(B, T, H, dk), p["conv_q"])
    k = _conv_silu(_mm(h, p["wk"]).reshape(B, T, H, dk), p["conv_k"])
    v = _conv_silu(_mm(h, p["wv"]).reshape(B, T, H, dv), p["conv_v"])
    q, k = _unit(q) * dk ** -0.5, _unit(k)
    g = -jnp.exp(p["A_log"].astype(_F32)) * jax.nn.softplus(
        _mm(h, p["wa"]) + p["dt_bias"].astype(_F32))          # (B, T, H)
    beta = jax.nn.sigmoid(_mm(h, p["wb"])) * (2.0 if neg_eigval else 1.0)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs     # (B, H, dk | dv) and (B, H)
        S = jnp.exp(g_t)[..., None, None] * S
        seen = jnp.sum(S * k_t[..., None], axis=-2)            # S'^T k
        S = S + k_t[..., None] * (b_t[..., None] * (v_t - seen)
                                  )[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    _, o = lax.scan(token, jnp.zeros((B, H, dk, dv), _F32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = _rmsnorm(jnp.moveaxis(o, 0, 1), p["o_norm"], eps) \
        * jax.nn.silu(_mm(h, p["wg"]).reshape(B, T, H, dv))
    return _mm(o.reshape(B, T, H * dv), p["wo"].reshape(H * dv, -1))


@functools.partial(jax.jit, static_argnames=("n_kv_head", "head_dim", "eps"))
def _full_half(x, p, n_kv_head, head_dim, eps):
    return x + _rmsnorm(_attention(x, p["attn"], n_kv_head, head_dim, eps),
                        p["ln1"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("neg_eigval", "eps"))
def _linear_half(x, p, neg_eigval, eps):
    return x + _rmsnorm(_deltanet(x, p["lin"], neg_eigval, eps),
                        p["ln1"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _mlp_half(x, p, eps):
    m = p["mlp"]
    y = _mm(jax.nn.silu(_mm(x, m["w_gate"])) * _mm(x, m["w_up"]),
            m["w_down"])
    return x + _rmsnorm(y, p["ln2"]["scale"], eps)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, head, eps):
    return jnp.einsum("btd,vd->btv", _rmsnorm(x, ln_f["scale"], eps),
                      head.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, layer_types, n_kv_head: int,
           head_dim: int, neg_eigval: bool = True, eps: float = 1e-6):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32, a host
    array; the head's padded rows are left out.  `layer_types` names
    each layer of ``params["layers"]`` "full_attention" or
    "linear_attention"."""
    x = _embed(params["wte"], tokens)
    for p, kind in zip(params["layers"], layer_types):
        if str(kind) == "full_attention":
            x = _full_half(x, p, int(n_kv_head), int(head_dim), float(eps))
        else:
            x = _linear_half(x, p, bool(neg_eigval), float(eps))
        x = _mlp_half(x, p, float(eps))
    # the head a block of positions at a time, the logits gathered on
    # the host (reference/laguna.py)
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
