"""Autoregressive KV-cache decoding for the LLaMA family.

The programs are `kv_decode.py`'s, as GPT-2's are (gpt2_decode.py).
This module is the llama block they run over: RMSNorm, RoPE applied at
each token's logical position, grouped-query attention (the cache
stores the kv heads only, pre-repeat and post-RoPE — GQA's memory win
is exactly here: cache bytes scale with n_kv_head, not n_head), SwiGLU,
untied lm_head; and their binding under the family's public names.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu._private import scopes
from ray_tpu.models import kv_decode
from ray_tpu.models.decode_common import generator
from ray_tpu.models.layers import plain_rmsnorm
from ray_tpu.models.llama import LlamaConfig, rope_frequencies

__all__ = ["llama_init_cache", "llama_init_paged_cache",
           "llama_prefill", "llama_paged_prefill", "llama_decode_step",
           "llama_verify_step", "llama_generate"]


@jax.named_scope(scopes.LN)
def _norm(x, scale, cfg: LlamaConfig):
    return plain_rmsnorm(x, scale, cfg.rms_eps)


def _rope(x, cos, sin):
    """Rotate x (*lead, H, hd) by each position's own table rows
    (*lead, hd/2) — llama.apply_rope's (T, hd/2) tables assume every
    row shares one position ladder, which ragged rows do not."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[..., None, :]
    s = sin[..., None, :]
    out = jnp.stack([x1 * c - x2 * s, x1 * s + x2 * c],
                    axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _embed(params, tokens, cfg: LlamaConfig):
    return params["wte"].astype(cfg.dtype)[tokens]


def _place(x, params, pos_ids, cfg: LlamaConfig):
    cos, sin = rope_frequencies(cfg.max_seq, cfg.head_dim, cfg.rope_theta)
    return x, (cos[pos_ids], sin[pos_ids])               # (*lead, hd/2)


def _qkv(x, p, cfg: LlamaConfig, positions):
    d, h, kv, hd = (cfg.d_model, cfg.n_head, cfg.n_kv_head,
                    cfg.head_dim)
    lead = x.shape[:-1]
    xa = _norm(x, p["ln1"]["scale"], cfg).astype(cfg.dtype)
    with jax.named_scope(scopes.ATTN):
        q = (xa @ p["attn"]["wq"].astype(cfg.dtype).reshape(d, h * hd)
             ).reshape(*lead, h, hd)
        k = (xa @ p["attn"]["wk"].astype(cfg.dtype).reshape(d, kv * hd)
             ).reshape(*lead, kv, hd)
        v = (xa @ p["attn"]["wv"].astype(cfg.dtype).reshape(d, kv * hd)
             ).reshape(*lead, kv, hd)
        return _rope(q, *positions), _rope(k, *positions), v


@jax.named_scope(scopes.ATTN)
def _attend(q, ck, cv, mask, cfg: LlamaConfig):
    # grouped-query attention against the kv-head cache: query heads
    # reshape to (kv, group) — no head repetition needed
    kv, hd = cfg.n_kv_head, cfg.head_dim
    one = q.ndim == 3                   # one query a row, or T of them
    qg = q.reshape(*q.shape[:-2], kv, cfg.n_head // kv, hd)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs" if one else "btkgd,bskd->bkgts", qg,
        ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bkgs,bskd->bkgd" if one else "bkgts,bskd->btkgd",
                      probs, cv)


def _mix(x, o, p, cfg: LlamaConfig):
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    with jax.named_scope(scopes.ATTN):
        wo = p["attn"]["wo"].astype(cfg.dtype).reshape(h * hd, d)
        x = x + (o.reshape(*x.shape[:-1], h * hd) @ wo).astype(x.dtype)
    xm = _norm(x, p["ln2"]["scale"], cfg).astype(cfg.dtype)
    with jax.named_scope(scopes.MLP):
        gate = xm @ p["mlp"]["w_gate"].astype(cfg.dtype)
        up = xm @ p["mlp"]["w_up"].astype(cfg.dtype)
        hmid = jax.nn.silu(gate) * up
        return x + (hmid @ p["mlp"]["w_down"].astype(cfg.dtype)
                    ).astype(x.dtype)


def _norm_f(x, params, cfg: LlamaConfig):
    return _norm(x, params["ln_f"]["scale"], cfg)


@jax.named_scope(scopes.LM_HEAD)
def _lm_head(x, params, cfg: LlamaConfig):
    return (x.astype(cfg.dtype) @ params["lm_head"].astype(cfg.dtype)
            ).astype(jnp.float32)


BLOCK = kv_decode.Block(kv_heads=lambda cfg: cfg.n_kv_head, embed=_embed,
                        place=_place, qkv=_qkv, attend=_attend, mix=_mix,
                        norm_f=_norm_f, head=_lm_head)

# kv_decode's programs over the block (each documented there)
llama_init_cache = partial(kv_decode.init_cache, BLOCK)
llama_init_paged_cache = partial(kv_decode.init_paged_cache, BLOCK)
llama_prefill = partial(kv_decode.prefill, BLOCK)
llama_paged_prefill = partial(kv_decode.paged_prefill, BLOCK)
llama_decode_step = partial(kv_decode.decode_step, BLOCK)
llama_verify_step = partial(kv_decode.verify_step, BLOCK)
llama_generate = generator(llama_prefill, llama_decode_step,
                           llama_init_cache)
