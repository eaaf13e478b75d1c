"""Kimi-K2 forward and loss in plain ``jax.numpy``: the yardstick's copy.

Follows the published block (DeepSeek-AI 2024, "DeepSeek-V3 Technical
Report", sections 2.1.1 and 2.1.2; Hugging Face ``modeling_deepseek`` as
Kimi-K2's ``config.json`` selects it).  ``h = E[tokens]``; for layer
``i``: ``h <- h + MLA(RMSNorm(h))``, ``h <- h + FFN_i(RMSNorm(h))``;
logits ``RMSNorm(h) W_head^T`` (untied); no bias anywhere.

* MLA: ``c_q = RMSNorm(W_qa u)``; ``[q_nope | q_pe] = W_qb c_q`` per
  head; ``[c_kv | k_pe] = W_kva u``; ``c_kv <- RMSNorm(c_kv)``;
  ``q_pe, k_pe <- RoPE`` (one ``k_pe`` for all heads);
  ``k_nope = W_uk c_kv``, ``v = W_uv c_kv`` per head;
  ``score = (q_nope.k_nope + q_pe.k_pe) * s``, causal softmax;
  ``W_o``.  Always the EXPANDED form: no cache, nothing absorbed.
* RoPE with YaRN: ``inv_freq = f/factor * (1 - m) + f * m``,
  ``f_j = theta^(-2j/rope_dim)``, ``m`` one minus the linear ramp over
  the correction range of ``beta_fast`` / ``beta_slow``; ``s =
  qk_head_dim^-1/2 * (0.1 mscale_all_dim ln factor + 1)^2``.
* FFN: layer ``i < n_dense``: ``W_down(silu(W_gate m) * W_up m)``; the
  others ``sigma = sigmoid(m W_g)``, chosen = top-k of ``sigma + b``,
  ``w = sigma[chosen] / sum * route_scale``, ``y = sum_{e in chosen &
  held} w_e Expert_e(m) + Shared(m)``.

float32 throughout with ``precision="highest"``; no kernel, cache, sort
or grouped matmul: every held expert is applied DENSELY to every token
and weighted by a mask; nothing imported from ``ray_tpu.models``.

Departures, all about layout and memory and not mathematics: it reads
the program's parameter tree (layers stacked by kind, ``params["dense"]``
and ``params["moe"]``; ``W_kvb`` as ``wk_b`` and ``wv_b``; the held
experts stacked in the order of ``held``); rotary pairs are ``(2i,
2i+1)`` as the program's (the source's stored layout); attention runs in
blocks of queries, the dense experts in blocks of tokens, and weights
are upcast a matrix at a time, so that ``logits(params, tokens[1,
8704])`` at the published widths fits beside a serving engine.  What the
parameter tree does not show is stated by the caller
(``families/kimi_k2.py reference_kwargs``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
#: queries attended at once, tokens through the experts at once
_Q_BLOCK = 256
_T_BLOCK = 2048


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale.astype(_F32)


def _mm(x, w):
    """x (..., a) @ w (a, b), the weight upcast here, one at a time."""
    return jnp.einsum("...a,ab->...b", x, w.astype(_F32), precision=_HI)


def _blocks(n: int, size: int) -> int:
    return size if n % size == 0 else n


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(rope_dim, theta, factor, orig_max, beta_fast, beta_slow):
    f = theta ** (-jnp.arange(0, rope_dim, 2, dtype=_F32) / rope_dim)
    if factor <= 1:
        return f

    def pair_of(rotations):
        return rope_dim * math.log(orig_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), rope_dim - 1)
    ramp = jnp.clip((jnp.arange(rope_dim // 2, dtype=_F32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return f / factor * (1.0 - keep) + f * keep


def _rope(x, cos, sin):
    """x (..., T, [H,] r): pairs (2i, 2i+1) rotate."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _mla(u, p, eps, nope, rope):
    """u (B, T, d) -> (B, T, d): expanded latent attention, causal."""
    B, T, _ = u.shape
    cq = _rmsnorm(_mm(u, p["wq_a"]), p["q_norm"], eps)
    q = jnp.einsum("btr,rhk->bthk", cq, p["wq_b"].astype(_F32),
                   precision=_HI)
    kv = _mm(u, p["wkv_a"])
    c = p["kv_norm"].shape[0]
    ckv = _rmsnorm(kv[..., :c], p["kv_norm"], eps)
    ang = jnp.arange(T, dtype=_F32)[:, None] * rope["inv_freq"]
    cos, sin = jnp.cos(ang) * rope["gain"], jnp.sin(ang) * rope["gain"]
    q_pe = _rope(q[..., nope:], cos[:, None], sin[:, None])
    k_pe = _rope(kv[..., c:], cos, sin)
    k_nope = jnp.einsum("bsc,chn->bshn", ckv, p["wk_b"].astype(_F32),
                        precision=_HI)
    v = jnp.einsum("bsc,chv->bshv", ckv, p["wv_b"].astype(_F32),
                   precision=_HI)
    qb = _blocks(T, _Q_BLOCK)

    def queries(i):
        at = i * qb + jnp.arange(qb)
        qn = lax.dynamic_slice_in_dim(q[..., :nope], i * qb, qb, axis=1)
        qp = lax.dynamic_slice_in_dim(q_pe, i * qb, qb, axis=1)
        s = (jnp.einsum("bqhn,bshn->bhqs", qn, k_nope, precision=_HI)
             + jnp.einsum("bqhr,bsr->bhqs", qp, k_pe, precision=_HI)
             ) * rope["scale"]
        causal = at[:, None] >= jnp.arange(T)[None, :]
        w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("bhqs,bshv->bqhv", w, v, precision=_HI)

    o = lax.map(queries, jnp.arange(T // qb))        # (nq, B, qb, H, v)
    o = jnp.moveaxis(o, 0, 1).reshape(B, T, -1)
    return _mm(o, p["wo"].reshape(-1, p["wo"].shape[-1]))


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
    for place, e in enumerate(held):
        mine = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)   # (N,)
        one = {k: v[place] for k, v in p["experts"].items()}
        y = y + mine[:, None] * _swiglu(m, one)
    return y


def _one_layer(stack, index):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, index, axis=0,
                                           keepdims=False), stack)


def _rope_numbers(qk_nope_dim, qk_rope_dim, rope_theta, rope_factor,
                  rope_orig_max, beta_fast, beta_slow, mscale,
                  mscale_all_dim):
    return {"inv_freq": _inv_freq(qk_rope_dim, rope_theta, rope_factor,
                                  rope_orig_max, beta_fast, beta_slow),
            "gain": _mscale(rope_factor, mscale)
            / _mscale(rope_factor, mscale_all_dim),
            "scale": (qk_nope_dim + qk_rope_dim) ** -0.5
            * _mscale(rope_factor, mscale_all_dim) ** 2}


@functools.partial(jax.jit, static_argnames=("eps", "nope", "rope"))
def _attn_half(x, stack, index, eps, nope, rope):
    p = _one_layer(stack, index)
    rope = dict(rope)               # hashable for jit: a tuple of pairs
    rope["inv_freq"] = jnp.asarray(rope["inv_freq"], _F32)
    return x + _mla(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"], eps,
                    nope, rope)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_half(x, stack, index, eps):
    p = _one_layer(stack, index)
    return x + _swiglu(_rmsnorm(x, p["ln2"]["scale"], eps), p["mlp"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "held", "top_k", "norm_topk", "route_scale"))
def _expert_half(x, stack, index, eps, held, top_k, norm_topk,
                 route_scale):
    p = _one_layer(stack, index)
    B, T, d = x.shape
    m = _rmsnorm(x, p["ln2"]["scale"], eps).reshape(B * T, d)
    tb = _blocks(B * T, _T_BLOCK)
    y = lax.map(lambda rows: _experts(rows, p["moe"], held, top_k,
                                      norm_topk, route_scale),
                m.reshape(-1, tb, d))
    return x + y.reshape(B, T, d)


@jax.jit
def _embed(wte, tokens):
    return wte[tokens].astype(_F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, head, eps):
    return jnp.einsum("btd,vd->btv", _rmsnorm(x, ln_f["scale"], eps),
                      head.astype(_F32), precision=_HI)


def logits(params, tokens, *, vocab_size: int, held, top_k: int,
           qk_nope_dim: int, qk_rope_dim: int, rope_theta: float,
           rope_factor: float, rope_orig_max: int, beta_fast: float,
           beta_slow: float, mscale: float = 1.0,
           mscale_all_dim: float = 1.0, norm_topk: bool = True,
           route_scale: float = 1.0, eps: float = 1e-5):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32; the
    head's padded rows are left out.  `held` names, in the order of the
    stacked expert weights, which of the router's experts they are."""
    n_dense = params["dense"]["ln1"]["scale"].shape[0]
    n_moe = params["moe"]["ln1"]["scale"].shape[0]
    numbers = _rope_numbers(qk_nope_dim, qk_rope_dim, rope_theta,
                            rope_factor, rope_orig_max, beta_fast,
                            beta_slow, mscale, mscale_all_dim)
    # hashable for the jitted halves: the frequencies as a tuple
    rope = tuple(sorted({**numbers, "inv_freq": tuple(
        float(f) for f in numbers["inv_freq"])}.items()))
    held = tuple(int(e) for e in held)
    x = _embed(params["wte"], tokens)
    for i in range(n_dense):
        x = _attn_half(x, params["dense"], jnp.int32(i), eps, qk_nope_dim,
                       rope)
        x = _dense_half(x, params["dense"], jnp.int32(i), eps)
    for j in range(n_moe):
        x = _attn_half(x, params["moe"], jnp.int32(j), eps, qk_nope_dim,
                       rope)
        x = _expert_half(x, params["moe"], jnp.int32(j), eps, held, top_k,
                         norm_topk, route_scale)
    return _head(x, params["ln_f"], params["head"][:vocab_size], eps)


def loss(params, tokens, *, vocab_size: int, **stated):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size, **stated)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
