"""Laguna family decoder (``model_type: laguna``): window and full
attention layers of different head counts, a per-head output gate, and
a sparse expert layer that holds every expert.

Same template as gpt2.py / llama.py / jamba.py / kimi_k2.py (pure
init/apply over pytrees, logical axes, bf16 compute over float32 or
bf16 weights).  A layer is one of two KINDS by its attention
(``cfg.layer_types``): ``full`` attends the whole context, ``window``
the last ``cfg.window`` positions, itself included; the kinds differ in
their number of query heads (``cfg.heads_per_layer``) and in their
rotary settings, and share the K/V heads and the head size.  The layers
are a LIST (``params["layers"]``) walked unrolled: two kinds of
different shapes in one published period of four do not stack, and a
pipeline stage holds few layers.

The layer equations, ``u = RMSNorm(h)``, no bias anywhere:

  * ``q = u W_q`` (H_l heads of ``head_dim``), ``k = u W_k``, ``v = u
    W_v`` (``n_kv_head`` heads); query head ``h`` reads K/V head ``h //
    (H_l / n_kv_head)``.  K and V of one token are kept FOLDED, ``n_kv_head
    * head_dim`` wide in one row (1,024 lanes at the published sizes: a
    cache row is whole lane tiles, and a K/V head is a lane slice of
    it).
  * RoPE, pairs ``(2i, 2i+1)`` as `models/llama.py apply_rope` pairs
    them (`layers.rotate`).  A full layer rotates the first
    ``full_rotary_dim`` dims of each head with YaRN's frequencies
    (`layers.yarn_inv_freq`'s arithmetic) and multiplies cos and sin
    by ``attention_factor``; the other dims pass.  A window layer
    rotates the whole head at base ``window_rope_theta``, unscaled.
  * ``score = q.k / sqrt(head_dim)`` over ``j <= i`` (full) or ``i -
    window < j <= i`` (window), float32 softmax.
  * gate: ``g = sigmoid(u W_g)``, one scalar a head a token;
    ``a = concat_h(g_h o_h) W_o``.
  * FFN (``cfg.mlp_types``): ``dense``: ``W_down(silu(W_gate m) * W_up
    m)`` of width ``d_ff``; ``sparse``: `experts.moe_layer` on ``m =
    RMSNorm(h)`` in float32, softmax scores over all experts, top-k
    renormalised, times ``route_scale``, ``held`` = all of them.
  * logits ``= RMSNorm(h) W_head^T``: the head is NOT tied.
"""

from __future__ import annotations

import dataclasses
import math
import types
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import scopes
from ray_tpu.models import experts as ex
from ray_tpu.models.banded_attention import attend_masked, attn_out
# the norm, the SwiGLU MLP, the embedding lookup and the untied head
# read `dtype` and `rms_eps` off whichever config they are handed
from ray_tpu.models.layers import (embed, lm_logits, nll_from_logits,
                                   rmsnorm, rotate, swiglu, yarn_inv_freq)
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)

FULL, WINDOW = "full", "window"
DENSE, SPARSE = "dense", "sparse"
#: the scope a layer's attention (projections, rotary, scores, gate,
#: output projection) is timed under, by kind
ATTN_SCOPE = {FULL: scopes.ATTN_FULL, WINDOW: scopes.ATTN_WINDOW}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100_352
    max_seq: int = 4096
    d_model: int = 2048
    head_dim: int = 128
    n_kv_head: int = 8
    #: the source's one count of query heads (its full layers'); each
    #: layer runs at its own, ``heads_per_layer``
    n_head: int = 48
    #: each layer's attention kind, query heads and FFN kind
    layer_types: Tuple[str, ...] = (FULL, WINDOW, WINDOW, WINDOW, FULL)
    heads_per_layer: Tuple[int, ...] = (48, 64, 64, 64, 48)
    mlp_types: Tuple[str, ...] = (DENSE, SPARSE, SPARSE, SPARSE, SPARSE)
    window: int = 512
    d_ff: int = 8192
    d_expert: int = 512
    n_routed: int = 256
    top_k: int = 8
    n_shared: int = 1
    scoring: str = "softmax"
    norm_topk: bool = True
    route_scale: float = 2.5
    #: full layers: YaRN over the first ``full_rotary_dim`` dims
    full_rotary_dim: int = 64
    full_rope_theta: float = 500_000.0
    rope_factor: float = 64.0
    rope_orig_max: int = 4096
    beta_fast: float = 64.0
    beta_slow: float = 1.0
    attention_factor: float = 1.4158883083359672
    #: window layers: the whole head, unscaled
    window_rope_theta: float = 10_000.0
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_pad_to: int = 128
    #: queries and keys a tile of the prefill's banded attention
    attn_block: int = 512
    #: most rows of grouped assignments one pass of the experts takes:
    #: a 4k prefill's 32,768 in one pass, an 8k prefill's in two (every
    #: pass walks all the tokens and multiplies a row tile for each of
    #: the 256 groups, so few passes of many rows; an odd multiple of
    #: 256, `experts.tile_rows`); 0.27 GB of float32 rows each way
    moe_tile_rows: int = 33_024
    #: taken and not read: the harness's rehearsal lays it over every
    #: family's overrides, and this family has one attention path
    use_flash: Optional[bool] = None

    def __post_init__(self):
        n = len(self.layer_types)
        if len(self.heads_per_layer) != n or len(self.mlp_types) != n:
            raise ValueError(
                f"layer_types, heads_per_layer and mlp_types must name "
                f"the same {n} layers")
        if not set(self.layer_types) <= {FULL, WINDOW}:
            raise ValueError(f"layer_types must be {FULL!r} or "
                             f"{WINDOW!r}, got {self.layer_types}")
        if not set(self.mlp_types) <= {DENSE, SPARSE}:
            raise ValueError(f"mlp_types must be {DENSE!r} or "
                             f"{SPARSE!r}, got {self.mlp_types}")
        if self.n_head not in self.heads_per_layer:
            raise ValueError(f"n_head {self.n_head} is no layer's count "
                             f"of {self.heads_per_layer}")
        if any(h % self.n_kv_head for h in self.heads_per_layer):
            raise ValueError(f"every layer's heads {self.heads_per_layer} "
                             f"must be a multiple of n_kv_head="
                             f"{self.n_kv_head}")
        if self.full_rotary_dim % 2 or self.head_dim % 2 \
                or self.full_rotary_dim > self.head_dim:
            raise ValueError("rotary dims must be even and within a head")
        self.experts  # its own checks

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The indices of the layers that attend as `kind`."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == kind)

    @property
    def kv_width(self) -> int:
        """One token's K (or V) of one layer, folded into one row."""
        return self.n_kv_head * self.head_dim

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p

    @property
    def experts(self) -> ex.ExpertsConfig:
        return ex.ExpertsConfig(
            d_model=self.d_model, d_expert=self.d_expert,
            n_routed=self.n_routed, top_k=self.top_k, held=None,
            scoring=self.scoring, norm_topk=self.norm_topk,
            route_scale=self.route_scale, n_shared=self.n_shared,
            dtype=self.dtype, param_dtype=self.param_dtype,
            tile_rows=self.moe_tile_rows)


_PRESETS: Dict[str, Dict[str, Any]] = {
    # both kinds at 6 / 8 query heads over 2 K/V heads, a window that a
    # short prompt wraps several times
    "nano": dict(vocab_size=512, max_seq=128, d_model=64, head_dim=16,
                 n_kv_head=2, n_head=6, layer_types=(FULL, WINDOW, FULL),
                 heads_per_layer=(6, 8, 6),
                 mlp_types=(DENSE, SPARSE, SPARSE), window=8, d_ff=128,
                 d_expert=32, n_routed=16, top_k=4, full_rotary_dim=8,
                 rope_orig_max=32, rope_factor=4.0,
                 attention_factor=0.1 * math.log(4.0) + 1.0,
                 attn_block=16),
    # layers 0-4 of the published 40: the leading dense layer and one
    # period of four behind it
    "laguna-xs2": {},
}


def laguna_config(name: str = "laguna-xs2", **overrides) -> LagunaConfig:
    """`overrides` may give the per-layer lists as any sequences."""
    kw = dict(_PRESETS[name], **overrides)
    for key in ("layer_types", "heads_per_layer", "mlp_types"):
        if key in kw:
            kw[key] = tuple(kw[key])
    return LagunaConfig(**kw)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _attn_params(cfg: LagunaConfig, heads: int) -> int:
    d, hd = cfg.d_model, cfg.head_dim
    return 2 * d * heads * hd + 2 * d * cfg.kv_width + d * heads


def laguna_param_count(cfg: LagunaConfig) -> int:
    """Embedding and head (untied), the final norm, and per layer its
    attention (q, k, v, gate, o), two norms and its FFN."""
    d = cfg.d_model
    total = 2 * cfg.vocab_size * d + d
    for heads, mlp in zip(cfg.heads_per_layer, cfg.mlp_types):
        total += _attn_params(cfg, heads) + 2 * d + (
            3 * d * cfg.d_ff if mlp == DENSE
            else ex.experts_param_count(cfg.experts))
    return total


def laguna_logical_axes(cfg: LagunaConfig) -> Dict[str, Any]:
    """Pytree (matching laguna_init's) of logical-axis tuples."""
    def layer(mlp):
        axes = {"ln1": {"scale": ("embed",)}, "ln2": {"scale": ("embed",)},
                "attn": {"wq": ("embed", "heads", "head_dim"),
                         "wk": ("embed", None), "wv": ("embed", None),
                         "wg": ("embed", "heads"),
                         "wo": ("heads", "head_dim", "embed")}}
        if mlp == DENSE:
            axes["mlp"] = {"w_gate": ("embed", "mlp"),
                           "w_up": ("embed", "mlp"),
                           "w_down": ("mlp", "embed")}
        else:
            axes["moe"] = ex.experts_logical_axes(cfg.experts)
        return axes

    return {"wte": ("vocab", "embed"), "head": ("vocab", "embed"),
            "ln_f": {"scale": ("embed",)},
            "layers": [layer(mlp) for mlp in cfg.mlp_types]}


def laguna_init(key, cfg: LagunaConfig) -> Dict[str, Any]:
    """Seeded weights: projections N(0, 0.02), those into the residual
    stream scaled by 1/sqrt(2 n_layer), norms 1; the router float32
    (experts.experts_init; its selection bias is zeros here: the source
    states none, and softmax scoring does not read it).  Every tensor is
    drawn by a program of its own, so a float32 draw is never whole
    beside the weights."""
    d, hd = cfg.d_model, cfg.head_dim
    pd = cfg.param_dtype
    std = 0.02
    res_std = std / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 3 + 9 * cfg.n_layer))

    def normal(shape, s=std):
        return jax.jit(lambda k: (jax.random.normal(
            k, shape, jnp.float32) * s).astype(pd))(next(keys))

    def layer(heads, mlp):
        p = {"ln1": {"scale": jnp.ones((d,), pd)},
             "ln2": {"scale": jnp.ones((d,), pd)},
             "attn": {"wq": normal((d, heads, hd)),
                      "wk": normal((d, cfg.kv_width)),
                      "wv": normal((d, cfg.kv_width)),
                      "wg": normal((d, heads)),
                      "wo": normal((heads, hd, d), res_std)}}
        if mlp == DENSE:
            p["mlp"] = {"w_gate": normal((d, cfg.d_ff)),
                        "w_up": normal((d, cfg.d_ff)),
                        "w_down": normal((cfg.d_ff, d), res_std)}
        else:
            moe = jax.jit(lambda k: ex.experts_init(
                k, cfg.experts, std=std, out_std=res_std))(next(keys))
            moe["router"]["bias"] = jnp.zeros_like(moe["router"]["bias"])
            p["moe"] = moe
        return p

    return {"wte": normal((cfg.padded_vocab, d)),
            "head": normal((cfg.padded_vocab, d)),
            "ln_f": {"scale": jnp.ones((d,), pd)},
            "layers": [layer(h, m) for h, m in zip(cfg.heads_per_layer,
                                                   cfg.mlp_types)]}


# ---------------------------------------------------------------------------
# rotary
# ---------------------------------------------------------------------------

def _full_inv_freq(cfg: LagunaConfig) -> np.ndarray:
    """YaRN's frequencies of a full layer's rotated dims
    (`layers.yarn_inv_freq`, which reads these names)."""
    return yarn_inv_freq(types.SimpleNamespace(
        qk_rope_dim=cfg.full_rotary_dim, rope_theta=cfg.full_rope_theta,
        rope_factor=cfg.rope_factor, rope_orig_max=cfg.rope_orig_max,
        beta_fast=cfg.beta_fast, beta_slow=cfg.beta_slow))


def rope_tables(positions, cfg: LagunaConfig, kind: str):
    """(cos, sin, rotated dims) of a layer of `kind` at int `positions`
    (...): cos, sin (..., dims / 2) float32, a full layer's multiplied
    by ``attention_factor``."""
    pos = positions.astype(jnp.float32)[..., None]
    if kind == FULL:
        ang = pos * _full_inv_freq(cfg)
        return (jnp.cos(ang) * cfg.attention_factor,
                jnp.sin(ang) * cfg.attention_factor, cfg.full_rotary_dim)
    hd = cfg.head_dim
    ang = pos * (cfg.window_rope_theta ** (
        -np.arange(0, hd, 2, dtype=np.float64) / hd)).astype(np.float32)
    return jnp.cos(ang), jnp.sin(ang), hd


def apply_rope(x, cos, sin, dims: int):
    """x (..., heads, head_dim) with cos, sin (..., dims / 2): the first
    `dims` of each head rotate, the others pass."""
    cos, sin = cos[..., None, :], sin[..., None, :]
    if dims == x.shape[-1]:
        return rotate(x, cos, sin)
    return jnp.concatenate([rotate(x[..., :dims], cos, sin),
                            x[..., dims:]], axis=-1)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def project(u, p, cfg: LagunaConfig, kind: str, positions):
    """u (..., d) normed input at int `positions` (...) -> q (..., H,
    hd) rotated, k and v (..., kv_width) folded (k rotated), gate (...,
    H) float32 in (0, 1)."""
    dt = cfg.dtype
    u = u.astype(dt)
    lead = u.shape[:-1]
    cos, sin, dims = rope_tables(positions, cfg, kind)
    q = (u @ p["wq"].astype(dt).reshape(cfg.d_model, -1)).reshape(
        *lead, -1, cfg.head_dim)
    k = (u @ p["wk"].astype(dt)).reshape(*lead, cfg.n_kv_head,
                                         cfg.head_dim)
    q = apply_rope(q, cos, sin, dims)
    k = apply_rope(k, cos, sin, dims).reshape(*lead, cfg.kv_width)
    v = u @ p["wv"].astype(dt)
    gate = jax.nn.sigmoid((u @ p["wg"].astype(dt)).astype(jnp.float32))
    return q, k, v, gate


def block(x, p, cfg: LagunaConfig, kind: str, positions,
          attend: Callable, valid=None, tiled: bool = True):
    """One layer of `kind` on x (..., d) at int `positions` (...).
    ``attend(q, k, v) -> o (..., H, hd)`` is the caller's: it owns the
    cache (and sees this layer's new rows, folded).  `valid` (...) marks
    the rows that hold a token (experts.routed_experts).  A layer of
    ``p`` with a ``"moe"`` entry is an expert layer.

    Returns (x, experts.STATS of the layer or None)."""
    u = rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
    with jax.named_scope(ATTN_SCOPE[kind]):
        q, k, v, gate = project(u, p["attn"], cfg, kind, positions)
    o = attend(q, k, v)
    with jax.named_scope(ATTN_SCOPE[kind]):
        x = x + attn_out(o, gate, p["attn"], cfg).astype(x.dtype)
    if "moe" not in p:
        return x + swiglu(rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps),
                          p["mlp"], cfg), None
    d = x.shape[-1]
    m = rmsnorm(x.astype(jnp.float32), p["ln2"]["scale"], cfg.rms_eps)
    y, stats = ex.moe_layer(
        p["moe"], m.reshape(-1, d), cfg.experts,
        None if valid is None else valid.reshape(-1), tiled)
    return x + y.reshape(x.shape).astype(x.dtype), stats


def walk_layers(cfg: LagunaConfig, params, x, layer: Callable):
    """`x` through the layers, unrolled.  ``layer(x, p, lidx, kind, j)
    -> (x, stats)`` is layer `lidx` of the model with weights `p`, the
    `j`-th of its kind.  Returns (x, the expert layers' stats (n_sparse,
    len(STATS)) or None)."""
    seen = {FULL: 0, WINDOW: 0}
    stats: List[Any] = []
    for lidx, (p, kind) in enumerate(zip(params["layers"],
                                         cfg.layer_types)):
        x, s = layer(x, p, lidx, kind, seen[kind])
        seen[kind] += 1
        if s is not None:
            stats.append(s)
    return x, jnp.stack(stats) if stats else None


def causal_mask(T: int, kind: str, cfg: LagunaConfig):
    """(T, T) bool: what position i of a layer of `kind` attends."""
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    return mask & (j > i - cfg.window) if kind == WINDOW else mask


def laguna_hidden(params, tokens, cfg: LagunaConfig, rules=DEFAULT_RULES):
    """tokens (B, T) -> final hidden (B, T, d): the full-sequence
    forward, no cache.  Every sorted assignment goes through one
    grouped matmul (``tiled=False``), so the forward differentiates."""
    B, T = tokens.shape
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    x = with_logical_constraint(embed(params, tokens, cfg),
                                ("batch", "seq", "embed"), rules)

    def layer(x, p, lidx, kind, j):
        def attend(q, k, v):
            with jax.named_scope(ATTN_SCOPE[kind]):
                return attend_masked(q, k, v,
                                     causal_mask(T, kind, cfg)[None], cfg)

        x, stats = block(x, p, cfg, kind, positions, attend, tiled=False)
        return with_logical_constraint(x, ("batch", "seq", "embed"),
                                       rules), stats

    return walk_layers(cfg, params, x, layer)[0]


def laguna_forward(params, tokens, cfg: LagunaConfig,
                   rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    hidden = laguna_hidden(params, tokens, cfg, rules)
    return with_logical_constraint(lm_logits(hidden, params, cfg),
                                   ("batch", "seq", "vocab"), rules)


def laguna_loss(params, batch, cfg: LagunaConfig,
                rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}, optionally {"mask"}.  No auxiliary balance
    loss: nothing here trains an expert layer."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    nll = nll_from_logits(laguna_forward(params, inputs, cfg, rules),
                          targets, cfg.vocab_size, cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


__all__ = ["LagunaConfig", "laguna_config", "laguna_init",
           "laguna_forward", "laguna_loss", "laguna_logical_axes",
           "laguna_param_count", "FULL", "WINDOW"]
