"""LLaMA-family decoder: RMSNorm + RoPE + SwiGLU + grouped-query
attention.

Same TPU-first template as gpt2.py (the reference framework ships no
models — this zoo exists because on TPU the framework owns the compute
path): pure init/apply over pytrees, layers stacked on a leading axis
and applied with one `lax.scan`, parameters annotated with logical
sharding axes so DP/FSDP/TP/SP come from the rule table, attention
dispatching to the pallas flash kernel, per-layer remat.

Architecture (Touvron et al. 2023 / the llama-2 lineage, public):
  * pre-RMSNorm (no biases anywhere),
  * rotary position embeddings applied to q/k (no learned positions),
  * SwiGLU MLP (gate ⊙ silu(up) → down, d_ff ≈ 8/3·d rounded),
  * grouped-query attention: n_kv_head ≤ n_head kv heads shared by
    query groups (kv repeated head-wise before the kernel — exact, and
    the repeat is free under the flash kernel's (B·H, T, D) layout).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.layers import (ce_config_problems, lm_head_nll,
                                   nll_from_logits, plain_rmsnorm)
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32_000
    max_seq: int = 2048
    n_layer: int = 8
    n_head: int = 8
    n_kv_head: int = 4
    d_model: int = 512
    d_ff: int = 1408              # ≈ 8/3 · d, rounded to a 128-multiple
    rope_theta: float = 10_000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    scan_unroll: int = 1
    use_flash: Optional[bool] = None    # None = auto (flash on TPU)
    vocab_pad_to: int = 128
    #: lm-head + CE implementation (layers.CE_IMPLS); the non-dense impls
    #: run against the TRANSPOSED (V, D) view of lm_head so one kernel
    #: serves tied and untied heads (the transpose+cast fuses into the
    #: bf16 tile staging — cheap next to the (B,T,V) logits it removes).
    ce_impl: str = "dense"
    vocab_tile: int = 8192
    ce_block_n: int = 256
    ce_block_v: int = 1024
    #: resident-kv flash dispatch knob (layers.FLASH_RESIDENT_MODES);
    #: RAYTPU_FLASH_RESIDENT overrides per-process.
    flash_resident: str = "auto"

    def __post_init__(self):
        problems = ce_config_problems(self.ce_impl, self.flash_resident)
        if problems:
            raise ValueError("invalid LlamaConfig: "
                             + "; ".join(problems))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p


_PRESETS = {
    # name: (n_layer, n_head, n_kv_head, d_model, d_ff)
    "nano": (2, 2, 1, 64, 192),
    "tiny": (4, 4, 2, 128, 384),
    "llama-s": (12, 12, 4, 768, 2048),     # GPT-2-small-class
    "llama-1b": (16, 32, 8, 2048, 5504),
    "llama-7b": (32, 32, 32, 4096, 11008),
}


def llama_config(name: str = "llama-s", **overrides) -> LlamaConfig:
    L, h, kv, d, f = _PRESETS[name]
    kw: Dict[str, Any] = dict(n_layer=L, n_head=h, n_kv_head=kv,
                              d_model=d, d_ff=f)
    if name in ("nano", "tiny"):
        kw.update(vocab_size=512, max_seq=128)
    kw.update(overrides)
    cfg = LlamaConfig(**kw)
    if cfg.n_head % cfg.n_kv_head:
        raise ValueError(f"n_head {cfg.n_head} must divide by "
                         f"n_kv_head {cfg.n_kv_head}")
    return cfg


def llama_param_count(cfg: LlamaConfig) -> int:
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    hd = cfg.head_dim
    attn = d * cfg.n_head * hd + 2 * d * cfg.n_kv_head * hd \
        + cfg.n_head * hd * d
    mlp = 3 * d * f
    per_layer = attn + mlp + 2 * d          # + two rmsnorm scales
    return 2 * cfg.vocab_size * d + L * per_layer + d


def llama_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree (matching llama_init's) of logical-axis tuples; leading
    None on block leaves is the stacked-layer axis."""
    return {
        "wte": ("vocab", "embed"),
        "lm_head": ("embed", "vocab"),
        "ln_f": {"scale": ("embed",)},
        "blocks": {
            "ln1": {"scale": (None, "embed")},
            "ln2": {"scale": (None, "embed")},
            "attn": {
                "wq": (None, "embed", "heads", "head_dim"),
                "wk": (None, "embed", "kv_heads", "head_dim"),
                "wv": (None, "embed", "kv_heads", "head_dim"),
                "wo": (None, "heads", "head_dim", "embed"),
            },
            "mlp": {
                "w_gate": (None, "embed", "mlp"),
                "w_up": (None, "embed", "mlp"),
                "w_down": (None, "mlp", "embed"),
            },
        },
    }


def llama_init(key, cfg: LlamaConfig) -> Dict[str, Any]:
    L, d, f = cfg.n_layer, cfg.d_model, cfg.d_ff
    h, kv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 12))
    std = 0.02
    res_std = std / math.sqrt(2 * L)

    def norm(kk, shape, s=std):
        return (jax.random.normal(kk, shape, dtype=jnp.float32)
                * s).astype(pd)

    return {
        "wte": norm(next(k), (cfg.padded_vocab, d)),
        "lm_head": norm(next(k), (d, cfg.padded_vocab)),
        "ln_f": {"scale": jnp.ones((d,), pd)},
        "blocks": {
            "ln1": {"scale": jnp.ones((L, d), pd)},
            "ln2": {"scale": jnp.ones((L, d), pd)},
            "attn": {
                "wq": norm(next(k), (L, d, h, hd)),
                "wk": norm(next(k), (L, d, kv, hd)),
                "wv": norm(next(k), (L, d, kv, hd)),
                "wo": norm(next(k), (L, h, hd, d), s=res_std),
            },
            "mlp": {
                "w_gate": norm(next(k), (L, d, f)),
                "w_up": norm(next(k), (L, d, f)),
                "w_down": norm(next(k), (L, f, d), s=res_std),
            },
        },
    }


def rope_frequencies(T: int, head_dim: int, theta: float):
    """(T, head_dim/2) cos/sin tables."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (B, T, H, D) with D even; rotate pairs (x_2i, x_2i+1)."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x1 * s + x2 * c
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _attention(x, p, cos, sin, cfg: LlamaConfig, rules):
    B, T, d = x.shape
    h, kv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    xc = x.astype(cfg.dtype)
    # flattened GEMMs (the measured-fast TPU form; see gpt2._attention)
    q = (xc @ p["wq"].astype(cfg.dtype).reshape(d, h * hd)
         ).reshape(B, T, h, hd)
    k = (xc @ p["wk"].astype(cfg.dtype).reshape(d, kv * hd)
         ).reshape(B, T, kv, hd)
    v = (xc @ p["wv"].astype(cfg.dtype).reshape(d, kv * hd)
         ).reshape(B, T, kv, hd)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    if kv != h:
        # GQA: each kv head serves h/kv query heads; the head-wise
        # repeat is exact and lays out contiguously for the kernel
        rep = h // kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    q = with_logical_constraint(q, ("batch", "seq", "heads",
                                    "head_dim"), rules)
    from ray_tpu.ops.attention import causal_attention

    o = causal_attention(q, k, v, use_flash=cfg.use_flash,
                         resident=cfg.flash_resident, rules=rules)
    o = o.reshape(B, T, h * hd)
    wo = p["wo"].astype(cfg.dtype).reshape(h * hd, d)
    return (o @ wo).astype(x.dtype)


def _mlp(x, p, cfg: LlamaConfig, rules):
    xc = x.astype(cfg.dtype)
    gate = xc @ p["w_gate"].astype(cfg.dtype)
    up = xc @ p["w_up"].astype(cfg.dtype)
    hidden = jax.nn.silu(gate) * up
    hidden = with_logical_constraint(hidden, ("batch", "seq", "mlp"),
                                     rules)
    return (hidden @ p["w_down"].astype(cfg.dtype)).astype(x.dtype)


def _block(x, p, cos, sin, cfg: LlamaConfig, rules):
    x = x + _attention(plain_rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps),
                       p["attn"], cos, sin, cfg, rules)
    x = x + _mlp(plain_rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps),
                 p["mlp"], cfg, rules)
    return with_logical_constraint(x, ("batch", "seq", "embed"),
                                   rules), None


def llama_hidden(params, tokens, cfg: LlamaConfig,
                 rules=DEFAULT_RULES):
    B, T = tokens.shape
    wte = with_logical_constraint(params["wte"].astype(cfg.dtype),
                                  (None, None), rules)
    x = wte[tokens]
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    cos, sin = rope_frequencies(T, cfg.head_dim, cfg.rope_theta)

    block = partial(_block, cos=cos, sin=sin, cfg=cfg, rules=rules)
    if cfg.remat:
        block = jax.checkpoint(
            block, policy=jax.checkpoint_policies.nothing_saveable)

    def scan_body(carry, layer_params):
        return block(carry, layer_params)

    x, _ = lax.scan(scan_body, x, params["blocks"],
                    unroll=cfg.scan_unroll)
    return plain_rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)


def llama_forward(params, tokens, cfg: LlamaConfig,
                  rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 → logits (B, T, padded_vocab) float32."""
    x = llama_hidden(params, tokens, cfg, rules)
    logits = jnp.einsum("btd,dv->btv", x,
                        params["lm_head"].astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return with_logical_constraint(logits, ("batch", "seq", "vocab"),
                                   rules)


def llama_loss(params, batch, cfg: LlamaConfig,
               rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}; padded-vocab tail masked (the gather-free
    NLL shared with gpt2)."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    if cfg.ce_impl != "dense":
        hidden = llama_hidden(params, inputs, cfg, rules)
        # (D, V) lm_head → the (V, D) vocab-major view the CE kernels
        # share with gpt2's tied wte
        nll = lm_head_nll(hidden, params["lm_head"].T, targets, cfg)
    else:
        logits = llama_forward(params, inputs, cfg, rules)
        nll = nll_from_logits(logits, targets, cfg.vocab_size,
                              cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)
