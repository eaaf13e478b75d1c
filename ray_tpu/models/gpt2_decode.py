"""Autoregressive decoding with a KV cache for the GPT-2 family.

The programs are `kv_decode.py`'s, the decoder of every family whose
cache is plain K/V (static max_seq cache in both layouts, one
full-sequence `prefill` dispatch, one compiled per-token step scanned
over stacked layers, per-sequence position vectors for ragged batches:
read them there).  This module is the GPT-2 block they run over —
learned positions added where a token is embedded, LayerNorm, one fused
q/k/v projection with biases, every head's K/V cached, GELU MLP, head
tied to the embedding — and their binding under the family's public
names.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu._private import scopes
from ray_tpu.models import kv_decode
from ray_tpu.models.decode_common import generator
from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.models.layers import layernorm

__all__ = ["init_cache", "init_paged_cache", "prefill", "paged_prefill",
           "decode_step", "verify_step", "generate"]


def _kv_heads(cfg: GPT2Config) -> int:
    if cfg.n_experts:
        raise NotImplementedError(
            "KV-cache decoding currently supports dense GPT-2 configs "
            "only (n_experts=0); MoE decode needs per-step routing")
    return cfg.n_head


def _embed(params, tokens, cfg: GPT2Config):
    return params["wte"].astype(cfg.dtype)[tokens]


def _place(x, params, pos_ids, cfg: GPT2Config):
    return x + params["wpe"].astype(cfg.dtype)[pos_ids], None


def _qkv(x, p, cfg: GPT2Config, positions):
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    xa = layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"])
    with jax.named_scope(scopes.ATTN):
        w = p["attn"]["qkv_w"].astype(cfg.dtype).reshape(d, 3 * h * hd)
        qkv = (xa @ w).reshape(*x.shape[:-1], 3, h, hd) \
            + p["attn"]["qkv_b"].astype(cfg.dtype)
        return qkv[..., 0, :, :], qkv[..., 1, :, :], qkv[..., 2, :, :]


@jax.named_scope(scopes.ATTN)
def _attend(q, ck, cv, mask, cfg: GPT2Config):
    one = q.ndim == 3                   # one query a row, or T of them
    scores = jnp.einsum("bhd,bshd->bhs" if one else "bthd,bshd->bhts",
                        q, ck).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(cfg.head_dim))
    scores = jnp.where(mask[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhs,bshd->bhd" if one else "bhts,bshd->bthd",
                      probs, cv)


@jax.named_scope(scopes.MLP)
def _mlp(xm, p, cfg: GPT2Config):
    """The block's MLP on normalised activations of any rank."""
    hmid = jax.nn.gelu(xm @ p["fc_w"].astype(cfg.dtype)
                       + p["fc_b"].astype(cfg.dtype))
    return (hmid @ p["proj_w"].astype(cfg.dtype)
            + p["proj_b"].astype(cfg.dtype))


def _mix(x, o, p, cfg: GPT2Config):
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    with jax.named_scope(scopes.ATTN):
        wo = p["attn"]["o_w"].astype(cfg.dtype).reshape(h * hd, d)
        x = x + (o.reshape(*x.shape[:-1], h * hd) @ wo
                 + p["attn"]["o_b"].astype(cfg.dtype))
    return x + _mlp(layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"]),
                    p["mlp"], cfg)


def _norm_f(x, params, cfg: GPT2Config):
    return layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])


@jax.named_scope(scopes.LM_HEAD)
def _lm_head(x, params, cfg: GPT2Config):
    """Final layernorm'd activations -> float32 logits (tied head)."""
    return (x @ params["wte"].astype(cfg.dtype).T).astype(jnp.float32)


BLOCK = kv_decode.Block(kv_heads=_kv_heads, embed=_embed, place=_place,
                        qkv=_qkv, attend=_attend, mix=_mix,
                        norm_f=_norm_f, head=_lm_head)

# kv_decode's programs over the block (each documented there)
init_cache = partial(kv_decode.init_cache, BLOCK)
init_paged_cache = partial(kv_decode.init_paged_cache, BLOCK)
prefill = partial(kv_decode.prefill, BLOCK)
paged_prefill = partial(kv_decode.paged_prefill, BLOCK)
decode_step = partial(kv_decode.decode_step, BLOCK)
verify_step = partial(kv_decode.verify_step, BLOCK)
generate = generator(prefill, decode_step, init_cache)
