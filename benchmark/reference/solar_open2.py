"""Solar-Open2 forward and loss in plain ``jax.numpy``: the yardstick's
copy.

Follows Upstage's published ``config.json`` (``model_type:
solar_open2``, Solar-Open2-250B) and, for the linear-attention layers,
Kimi Linear's KDA (arXiv 2510.26692), which the ``kda_*`` keys and
``linear_attn_config`` name.  ``h = E[tokens]``; for layer ``l``: ``h <-
h + Mixer_l(RMSNorm(h))``, ``h <- h + FFN_l(RMSNorm(h))``; logits
``RMSNorm(h) W_head^T`` (untied); no bias and no positions anywhere
(``use_rope: false``).

* A ``gqa`` layer.  ``q = u W_q`` (64 heads of ``head_dim``), ``k = u
  W_k``, ``v = u W_v`` (``n_kv_head`` heads; query head ``h`` reads K/V
  head ``h // (H / n_kv_head)``), no rotary, ``score = q.k /
  sqrt(head_dim)`` under an explicit (T, T) mask ``j <= i``, softmax;
  ``g = sigmoid(u W_g)``, one value a channel of each head, multiplies
  the head's output before ``W_o``.
* A ``kda`` layer, per head with keys and values of ``hd``: ``[q~ | k~ |
  v] = SiLU(conv(u W_qkv))`` with a causal depthwise convolution of the
  kernel the weights have, from zeros; ``q = q~ / |q~| * hd^-1/2``, ``k
  = k~ / |k~|`` (eps 1e-6); per-channel log-decay ``g = -exp(A_log_h) *
  softplus(u W_fa W_fb + dt_bias)``; ``beta = sigmoid(u W_beta)``,
  doubled where ``neg_eigval``.  The state ``S`` (hd, hd) starts at
  zero and takes the tokens ONE AT A TIME, in a scan over time: ``S' =
  Diag(exp(g_t)) S``; ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t
  = S^T q_t``.  No chunk, no triangular solve: the sequential recurrence
  is the definition the program's chunked form is held to.  ``out =
  concat_h(RMSNorm_h(o) * sigmoid(u W_ga W_gb)) W_o``.
* FFN, every layer: ``s = sigmoid(m W_r)`` over all experts, chosen =
  top-k of ``s + bias`` (the bias selects, it does not weigh), ``w =
  s[chosen] / sum * route_scale``, ``y = sum_{e in chosen & held} w_e
  Expert_e(m) + Shared(m)``: the experts this chip HOLDS (`held`), as
  the program is given them; what the others would have added is left
  out here as there.

float32 throughout with ``precision="highest"``; no kernel, cache, sort
or grouped matmul: every held expert is applied DENSELY to every token
and weighted by a mask; nothing imported from ``ray_tpu.models``.

What the config leaves open, and what is taken here as in the program
(``benchmark/configs/solar-open2.json`` ``assumed`` gives the reasons):
``use_gqa_gate`` is read per channel, from the normed input;
``kda_use_full_proj: false`` as the low-rank pairs of Kimi Linear; the
router as the DeepSeek-V3 lineage's, whose key names the config uses.

Departures, all about layout and memory and not mathematics: it reads
the program's parameter tree (a list of layers; K and V projections
folded ``(d, n_kv_head * head_dim)``; a KDA layer's three projections
and three convolutions stacked ``(d, 3, H, hd)`` / ``(K, 3, H, hd)``;
the experts stacked); attention runs in blocks of queries, each held
expert in turn over all tokens, the head in blocks of positions whose
logits are gathered on the host, and weights are upcast a matrix at a
time, so that ``logits(params, tokens[1, 8704])`` at the published
widths fits beside a serving engine.  What the parameter tree does not
show is stated by the caller (``families/solar_open2.py
reference_kwargs``).
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


def _heads(x, w):
    """x (B, T, a) @ w (a, H, hd) -> (B, T, H, hd)."""
    return jnp.einsum("bta,ahk->bthk", x, w.astype(_F32), precision=_HI)


def _blocks(n: int, size: int) -> int:
    return size if n % size == 0 else n


def _attention(u, p, n_kv_head, head_dim):
    """u (B, T, d) -> (B, T, d): one gated softmax layer, causal."""
    B, T, _ = u.shape
    kv, hd = n_kv_head, head_dim
    q = _heads(u, p["wq"])
    k = _mm(u, p["wk"]).reshape(B, T, kv, hd)
    v = _mm(u, p["wv"]).reshape(B, T, kv, hd)
    H = q.shape[2]
    qg = q.reshape(B, T, kv, H // kv, hd)
    qb = _blocks(T, _Q_BLOCK)

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
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, H, hd)
    o = o * jax.nn.sigmoid(_heads(u, p["wg"]))
    return _mm(o.reshape(B, T, H * hd), p["wo"].reshape(H * hd, -1))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _kda(u, p, neg_eigval, eps):
    """u (B, T, d) -> (B, T, d): one KDA layer from a zero state, its
    recurrence one token at a time."""
    B, T, _ = u.shape
    K, _, H, hd = p["conv_w"].shape
    x = jnp.einsum("btd,dchk->btchk", u, p["wqkv"].astype(_F32),
                   precision=_HI)
    # y_t = sum_i w_i x_{t - (K-1) + i}: the last tap meets the token
    # itself, what came before the sequence is zero
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0), (0, 0), (0, 0)))
    w = p["conv_w"].astype(_F32)
    qkv = jax.nn.silu(sum(xp[:, i:i + T] * w[i] for i in range(K)))
    q = _unit(qkv[:, :, 0]) * hd ** -0.5
    k, v = _unit(qkv[:, :, 1]), qkv[:, :, 2]
    g = -jnp.exp(p["A_log"].astype(_F32))[:, None] * jax.nn.softplus(
        _heads(_mm(u, p["wf_a"]), p["wf_b"]) + p["dt_bias"].astype(_F32))
    beta = jax.nn.sigmoid(_mm(u, p["wb"])) * (2.0 if neg_eigval else 1.0)

    def token(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs          # (B, H, hd) and b_t (B, H)
        S = jnp.exp(g_t)[..., None] * S
        seen = jnp.sum(S * k_t[..., None], axis=-2)            # S'^T k
        S = S + k_t[..., None] * (b_t[..., None] * (v_t - seen)
                                  )[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    _, o = lax.scan(token, jnp.zeros((B, H, hd, hd), _F32), tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = _rmsnorm(jnp.moveaxis(o, 0, 1), p["o_norm"], eps) \
        * jax.nn.sigmoid(_heads(_mm(u, p["wg_a"]), p["wg_b"]))
    return _mm(o.reshape(B, T, H * hd), p["wo"].reshape(H * hd, -1))


def _swiglu(m, p):
    return _mm(jax.nn.silu(_mm(m, p["w_gate"])) * _mm(m, p["w_up"]),
               p["w_down"])


def _experts(m, p, held, top_k, norm_topk, route_scale):
    """m (N, d): the held experts' part of the routed sum, each held
    expert applied to every token and weighted by whether the token
    chose it, plus the shared expert."""
    scores = jax.nn.sigmoid(_mm(m, p["router"]["w"]))
    _, chosen = lax.top_k(scores + p["router"]["bias"].astype(_F32), top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if norm_topk:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * route_scale
    y = _swiglu(m, p["shared"]) if "shared" in p else jnp.zeros_like(m)
    ids = jnp.asarray(held, jnp.int32)

    def one(place, y):
        mine = jnp.sum(jnp.where(chosen == ids[place], w, 0.0), axis=-1)
        weights = {k: lax.dynamic_index_in_dim(v, place, 0, keepdims=False)
                   for k, v in p["experts"].items()}
        return y + mine[:, None] * _swiglu(m, weights)

    return lax.fori_loop(0, len(held), one, y)


@functools.partial(jax.jit, static_argnames=("n_kv_head", "head_dim", "eps"))
def _gqa_half(x, p, n_kv_head, head_dim, eps):
    return x + _attention(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"],
                          n_kv_head, head_dim)


@functools.partial(jax.jit, static_argnames=("neg_eigval", "eps"))
def _kda_half(x, p, neg_eigval, eps):
    return x + _kda(_rmsnorm(x, p["ln1"]["scale"], eps), p["kda"],
                    neg_eigval, eps)


@functools.partial(jax.jit, static_argnames=(
    "eps", "held", "top_k", "norm_topk", "route_scale"))
def _expert_half(x, p, eps, held, top_k, norm_topk, route_scale):
    B, T, d = x.shape
    m = _rmsnorm(x, p["ln2"]["scale"], eps).reshape(B * T, d)
    return x + _experts(m, p["moe"], held, top_k, norm_topk,
                        route_scale).reshape(B, T, d)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, head, eps):
    return jnp.einsum("btd,vd->btv", _rmsnorm(x, ln_f["scale"], eps),
                      head.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, layer_types, n_kv_head: int,
           head_dim: int, held, top_k: int, neg_eigval: bool = True,
           norm_topk: bool = True, route_scale: float = 1.0,
           eps: float = 1e-5):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32, a host
    array; the head's padded rows are left out.  `layer_types` names
    each layer of ``params["layers"]`` "gqa" or "kda"; `held` names, in
    the order of the stacked expert weights, which of the router's
    experts they are."""
    held = tuple(int(e) for e in held)
    x = _embed(params["wte"], tokens)
    for p, kind in zip(params["layers"], layer_types):
        if str(kind) == "gqa":
            x = _gqa_half(x, p, int(n_kv_head), int(head_dim), float(eps))
        else:
            x = _kda_half(x, p, bool(neg_eigval), float(eps))
        x = _expert_half(x, p, float(eps), held, int(top_k),
                         bool(norm_topk), float(route_scale))
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
