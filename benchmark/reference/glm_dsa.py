"""GLM-5 forward and loss in plain ``jax.numpy``: the yardstick's copy.

The block is DeepSeek-V3's (DeepSeek-AI 2024, "DeepSeek-V3 Technical
Report", sections 2.1.1 and 2.1.2) with DeepSeek Sparse Attention in it
(DeepSeek-AI 2025, "DeepSeek-V3.2-Exp", and its reference
``inference/model.py class Indexer``), as ``zai-org/GLM-5``'s
``config.json`` (``model_type: glm_moe_dsa``) selects them.  ``h =
E[tokens]``; for layer ``i``: ``u = RMSNorm(h)``, ``h <- h + MLA_i(u)``,
``h <- h + FFN_i(RMSNorm(h))``; logits ``RMSNorm(h) W_head^T``
(untied); no bias but the index key's LayerNorm.

* MLA: ``c_q = RMSNorm(W_qa u)``; ``[q_nope | q_pe] = W_qb c_q`` per
  head; ``[c_kv | k_pe] = W_kva u``; ``c_kv <- RMSNorm(c_kv)``;
  ``q_pe, k_pe <- RoPE`` (one ``k_pe`` for all heads; plain RoPE,
  ``f_j = theta^(-2j/rope_dim)``); ``k_nope = W_uk c_kv``, ``v = W_uv
  c_kv`` per head; ``score = (q_nope.k_nope + q_pe.k_pe) *
  qk_head_dim^-1/2``.  Always the EXPANDED form: no cache, nothing
  absorbed.
* the indexer, ``J`` heads of ``D``, the first ``rope_dim`` of each
  ``D`` rotated by the same angles: ``q^I_{t,j} = RoPE((W^I_q
  c_q)_j)``; ``k^I_s = RoPE(LayerNorm(W^I_k u_s))`` (weight and bias,
  its own epsilon); ``w_{t,j} = (W^I_w u_t)_j J^-1/2 D^-1/2``;
  ``I_{t,s} = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)``.  ``S_t`` = the
  ``min(topk, t + 1)`` positions ``s <= t`` of highest ``I_{t,s}``
  (``lax.top_k``: ties to the lower position), and the softmax runs
  under the source's mask: ``-inf`` everywhere, 0 scattered at ``S_t``,
  on top of the causal ``-inf``.  Every layer over every position.
* FFN: layer ``i < n_dense``: ``W_down(silu(W_gate m) * W_up m)``; the
  others ``sigma = sigmoid(m W_g)``, chosen = top-k of ``sigma + b``,
  ``w = sigma[chosen] / sum * route_scale``, ``y = sum_{e in chosen &
  held} w_e Expert_e(m) + Shared(m)``.

float32 throughout with ``precision="highest"``; no kernel, cache, sort
of assignments or grouped matmul: every held expert is applied DENSELY
to every token and weighted by a mask; nothing imported from
``ray_tpu.models``.  No FP8 and no Hadamard rotation round the index
products (the source's code applies an orthogonal rotation to ``q^I``
and ``k^I`` to make its FP8 quantisation gentler; it leaves every ``q .
k`` as it is).

Departures, all about layout and memory and not mathematics: it reads
the program's parameter tree (layers stacked by kind, ``params["dense"]``
and ``params["moe"]``, each with ``attn``, ``indexer``, and ``mlp`` or
``moe``; ``W_kvb`` as ``wk_b`` and ``wv_b``; the held experts stacked in
the order of ``held``); rotary pairs are ``(2i, 2i+1)`` as the
program's; the selection and the attention run in blocks of queries,
the attention a group of heads at a time, the dense experts in blocks of
tokens, and weights are upcast a matrix at a time, so that
``logits(params, tokens[1, 12800])`` at the published widths fits
beside a serving engine.  What the parameter tree does not show is
stated by the caller (``families/glm_dsa.py reference_kwargs``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
#: queries selected for and attended at once, heads attended at once,
#: tokens through the experts at once
_Q_BLOCK = 128
_H_GROUP = 16
_T_BLOCK = 2048


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * scale.astype(_F32)


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale.astype(_F32) \
        + bias.astype(_F32)


def _mm(x, w):
    """x (..., a) @ w (a, b), the weight upcast here, one at a time."""
    return jnp.einsum("...a,ab->...b", x, w.astype(_F32), precision=_HI)


def _blocks(n: int, size: int) -> int:
    return size if n % size == 0 else n


def _rope(x, cos, sin):
    """x (..., T, [H,] r): pairs (2i, 2i+1) rotate."""
    pairs = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


def _index_parts(u, cq, p, cos, sin, rope, index_eps):
    """The indexer's queries (B, T, J, D), its one key a token (B, T,
    D) and the head weights (B, T, J); ``W^I_q`` is stored (latent, J *
    D), heads major."""
    J, D = p["ww"].shape[-1], p["wk"].shape[-1]
    qi = _mm(cq, p["wq"]).reshape(*cq.shape[:-1], J, D)
    qi = jnp.concatenate([_rope(qi[..., :rope], cos[:, None], sin[:, None]),
                          qi[..., rope:]], axis=-1)
    ki = _layernorm(_mm(u, p["wk"]), p["k_norm"]["scale"],
                    p["k_norm"]["bias"], index_eps)
    ki = jnp.concatenate([_rope(ki[..., :rope], cos, sin), ki[..., rope:]],
                         axis=-1)
    return qi, ki, _mm(u, p["ww"]) * (J ** -0.5 * D ** -0.5)


def _index_scores(qi, ki, w):
    """``I`` (B, Tq, S) of queries qi (B, Tq, J, D), w (B, Tq, J) over
    keys ki (B, S, D), every pair."""
    s = jnp.einsum("bqjd,bsd->bqjs", qi, ki, precision=_HI)
    return jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(s), w, precision=_HI)


def _selected(u, cq, p, cos, sin, rope, topk, index_eps):
    """The source's index mask as booleans: (B, T, T), True at ``S_t``."""
    B, T, _ = u.shape
    qi, ki, w = _index_parts(u, cq, p, cos, sin, rope, index_eps)
    qb = _blocks(T, _Q_BLOCK)
    k = min(topk, T)

    def queries(i):
        at = i * qb + jnp.arange(qb)
        score = _index_scores(
            lax.dynamic_slice_in_dim(qi, i * qb, qb, axis=1), ki,
            lax.dynamic_slice_in_dim(w, i * qb, qb, axis=1))
        causal = at[:, None] >= jnp.arange(T)[None, :]
        _, idx = lax.top_k(jnp.where(causal[None], score, -jnp.inf), k)
        picked = jnp.zeros((B, qb, T), bool).at[
            jnp.arange(B)[:, None, None], jnp.arange(qb)[None, :, None],
            idx].set(True)
        return picked & causal[None]

    m = lax.map(queries, jnp.arange(T // qb))           # (nq, B, qb, T)
    return jnp.moveaxis(m, 0, 1).reshape(B, T, T)


def _mla(u, p, pi, eps, nope, rope, theta, topk, index_eps):
    """u (B, T, d) -> (B, T, d): expanded latent attention under the
    causal mask and the indexer's."""
    B, T, _ = u.shape
    cq = _rmsnorm(_mm(u, p["wq_a"]), p["q_norm"], eps)
    kv = _mm(u, p["wkv_a"])
    c = p["kv_norm"].shape[0]
    ckv = _rmsnorm(kv[..., :c], p["kv_norm"], eps)
    inv = theta ** (-jnp.arange(0, rope, 2, dtype=_F32) / rope)
    ang = jnp.arange(T, dtype=_F32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    k_pe = _rope(kv[..., c:], cos, sin)
    keep = _selected(u, cq, pi, cos, sin, rope, topk, index_eps)
    H = p["wq_b"].shape[1]
    scale = (nope + rope) ** -0.5
    qb, hg = _blocks(T, _Q_BLOCK), _blocks(H, _H_GROUP)
    outs = []
    for h in range(0, H, hg):
        q = jnp.einsum("btr,rhk->bthk", cq,
                       p["wq_b"][:, h:h + hg].astype(_F32), precision=_HI)
        q_pe = _rope(q[..., nope:], cos[:, None], sin[:, None])
        k_nope = jnp.einsum("bsc,chn->bshn", ckv,
                            p["wk_b"][:, h:h + hg].astype(_F32),
                            precision=_HI)
        v = jnp.einsum("bsc,chv->bshv", ckv,
                       p["wv_b"][:, h:h + hg].astype(_F32), precision=_HI)

        def queries(i, q=q, q_pe=q_pe, k_nope=k_nope, v=v):
            qn = lax.dynamic_slice_in_dim(q[..., :nope], i * qb, qb, axis=1)
            qp = lax.dynamic_slice_in_dim(q_pe, i * qb, qb, axis=1)
            ok = lax.dynamic_slice_in_dim(keep, i * qb, qb, axis=1)
            s = (jnp.einsum("bqhn,bshn->bhqs", qn, k_nope, precision=_HI)
                 + jnp.einsum("bqhr,bsr->bhqs", qp, k_pe, precision=_HI)
                 ) * scale
            w = jax.nn.softmax(jnp.where(ok[:, None], s, -jnp.inf),
                               axis=-1)
            return jnp.einsum("bhqs,bshv->bqhv", w, v, precision=_HI)

        o = lax.map(queries, jnp.arange(T // qb))    # (nq, B, qb, hg, v)
        outs.append(jnp.moveaxis(o, 0, 1).reshape(B, T, hg, -1))
    o = jnp.concatenate(outs, axis=2).reshape(B, T, -1)
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


@functools.partial(jax.jit, static_argnames=(
    "eps", "nope", "rope", "theta", "topk", "index_eps"))
def _attn_half(x, stack, index, eps, nope, rope, theta, topk, index_eps):
    p = _one_layer({k: stack[k] for k in ("ln1", "attn", "indexer")},
                   index)
    return x + _mla(_rmsnorm(x, p["ln1"]["scale"], eps), p["attn"],
                    p["indexer"], eps, nope, rope, theta, topk, index_eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_half(x, stack, index, eps):
    p = _one_layer({k: stack[k] for k in ("ln2", "mlp")}, index)
    return x + _swiglu(_rmsnorm(x, p["ln2"]["scale"], eps), p["mlp"])


@functools.partial(jax.jit, static_argnames=(
    "eps", "held", "top_k", "norm_topk", "route_scale"))
def _expert_half(x, stack, index, eps, held, top_k, norm_topk,
                 route_scale):
    p = _one_layer({k: stack[k] for k in ("ln2", "moe")}, index)
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
           index_topk: int, index_eps: float = 1e-6,
           norm_topk: bool = True, route_scale: float = 1.0,
           eps: float = 1e-5):
    """tokens (B, T) int32 -> logits (B, T, vocab_size) float32; the
    head's padded rows are left out.  `held` names, in the order of the
    stacked expert weights, which of the router's experts they are."""
    n_dense = params["dense"]["ln1"]["scale"].shape[0]
    n_moe = params["moe"]["ln1"]["scale"].shape[0]
    held = tuple(int(e) for e in held)
    attn = dict(eps=eps, nope=qk_nope_dim, rope=qk_rope_dim,
                theta=float(rope_theta), topk=int(index_topk),
                index_eps=index_eps)
    x = _embed(params["wte"], tokens)
    for i in range(n_dense):
        x = _attn_half(x, params["dense"], jnp.int32(i), **attn)
        x = _dense_half(x, params["dense"], jnp.int32(i), eps)
    for j in range(n_moe):
        x = _attn_half(x, params["moe"], jnp.int32(j), **attn)
        x = _expert_half(x, params["moe"], jnp.int32(j), eps, held, top_k,
                         norm_topk, route_scale)
    return _head(x, params["ln_f"], params["head"][:vocab_size], eps)


def loss(params, tokens, *, vocab_size: int, **stated):
    """Mean next-token cross-entropy of tokens (B, T+1)."""
    lg = logits(params, tokens[:, :-1], vocab_size=vocab_size, **stated)
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
