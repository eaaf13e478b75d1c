"""Phi-4-mini-flash family (SambaY with differential attention): a
decoder whose second half attends the first half's cache.

Same template as jamba.py / laguna.py (pure init/apply over pytrees,
logical sharding axes, bf16 compute over float32 or bf16 weights).  The
architecture is SambaY (Ren et al. 2025, arXiv:2507.06607, "Decoder-
Hybrid-Decoder Architecture for Efficient Reasoning with Long
Generation"): a SELF-decoder of Mamba and sliding-window layers that
ends in one full-attention layer, and a CROSS-decoder (YOCO, Sun et al.
2024, arXiv:2405.05254) whose layers keep no cache of their own: half
of them attend the full layer's K/V, the other half gate a memory the
self-decoder's last Mamba layer left (the Gated Memory Unit).  Every
softmax is one half of a differential pair (Ye et al. 2024,
arXiv:2410.05258).

The layer equations.  ``n_layer = L`` layers (32), ``half = L / 2``.
``h <- h + mixer_i(LN(h))``, then ``h <- h + MLP(LN(h))``; ``LN`` is
LayerNorm with weight and bias; ``MLP(u) = (silu(g) * a) W_2`` with
``[g, a] = u W_1``, no bias.  No positions anywhere: no rotary, no
table.  Logits ``= LN_f(h) E^T``, tied.  By index ``i``:

  ============  ===============================================  =====
  ``i``         mixer                                            of 32
  ============  ===============================================  =====
  even, <= half  Mamba; layer ``half`` also emits the memory m      9
  odd, < half    differential attention over a window of 512        8
  half + 1       differential attention over everything; its K,     1
                 V are the model's only positional cache
  even, > half   Gated Memory Unit over m                           7
  odd, > half+1  differential CROSS-attention: own queries, layer   7
                 ``half + 1``'s K, V
  ============  ===============================================  =====

* Mamba (Mamba-1; mamba.mamba_mix WITHOUT Jamba's norms on dt, B, C):
  ``[x, z] = u W_in``; ``x <- silu(conv4(x) + b_c)``; ``[dt_r, B, C] = x
  W_x``; ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) (x) B_t`` (float32); ``y_t =
  s_t C_t + D x_t``; ``out = (y silu(z)) W_out``.  Layer ``half``'s
  ``m_t = y_t``: before the gate, with the D skip, in the compute dtype.
* differential attention: ``q = u W_q + b_q`` (n_head heads of hd), k, v
  likewise (n_kv_head heads); a cross layer computes q only.  Query
  pair p is heads (2p, 2p+1) = (q1, q2); K/V pair r is (k1, k2) = K
  heads (2r, 2r+1) and ``v_r = [v_2r ; v_2r+1]`` (2 hd wide); query pair
  p reads K/V pair ``p // (n_head / n_kv_head)``.  ``a^s = softmax_j(q^s
  . k^s(j) / sqrt(hd))`` in float32 over the allowed j, ``o^s = sum_j
  a^s(j) v_r(j)``; ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam_init(i)``, ``lam_init(i) = 0.8 - 0.6 exp(-0.3 i)``; ``o_p = (1 -
  lam_init(i)) RMSNorm_2hd(o^1 - lam o^2)``; ``out = concat_p(o_p) W_o +
  b_o``.  Allowed j for position t: a window layer ``t - window < j <=
  t``; the full and the cross layers ``j <= t``.
* Gated Memory Unit: ``out = (silu(u W_1) * m) W_2``, no bias, m the
  same position's memory.  It keeps nothing between two tokens.

**Differential attention as grouped-query attention over pair-heads.**
K and V are stored as they fall, ``kv_width = n_kv_head * hd`` lanes a
row: adjacent heads (2r, 2r+1) are then ONE pair-head of 2 hd lanes,
``[k1_r k2_r]`` and ``[v_2r v_2r+1]``.  Each query sub-head is padded to
2 hd lanes with zeros on the other half (``[q1, 0]``, ``[0, q2]``,
`pair_queries`): its score against the pair-head is ``q^s . k^s``, its
output the 2 hd-wide ``o^s``.  That is grouped-query attention with
``n_kv_head / 2`` K/V heads of ``2 hd``, ``n_head`` query heads and a
scale of ``1 / sqrt(hd)`` (`Phi4FlashConfig.pairs` is that geometry):
the walks the tree has (banded_attention.attend_banded and attend_rows,
ops/gqa_paged_decode.py) compute it as they are, at twice the score
products.  The combine, the norm and ``W_o`` follow outside
(`diff_out`).

The parameters are stacked by what repeats: ``params["self"]`` holds the
``half / 2`` (Mamba, window) pairs, ``params["cross"]`` the (GMU, cross)
pairs, each on a leading axis a ``lax.scan`` walks; ``params["memory"]``
and ``params["full"]`` are layers ``half`` and ``half + 1``.  A compiled
program holds each kind of layer once or twice, never 32 times.
"""

from __future__ import annotations

import dataclasses
import math
import types
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models import banded_attention, layers
from ray_tpu.models.layers import nll_from_logits, plain_rmsnorm
from ray_tpu.models.mamba import mamba_mix
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200_064
    max_seq: int = 4096
    n_layer: int = 32
    n_head: int = 40
    n_kv_head: int = 20
    d_model: int = 2560
    d_ff: int = 10_240
    window: int = 512
    #: every `mb_per_layer`-th layer is a Mamba (or, in the cross-
    #: decoder, a GMU) layer; the program has the one published value
    mb_per_layer: int = 2
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    ln_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    #: what the SSM state is KEPT in between two programs
    state_dtype: Any = jnp.float32
    vocab_pad_to: int = 128
    #: columns of the SSM scan's ``jnp`` chain computed at once
    scan_chunk: int = 32
    #: queries and keys a tile of the prefill's banded attention; it has
    #: to divide the context a replica is given (4,864 = 19 x 256), or a
    #: tile of queries would meet every key at once
    attn_block: int = 256
    #: taken and not read: the harness's rehearsal lays it over every
    #: family's overrides, and this family has one attention path
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if self.mb_per_layer != 2:
            raise ValueError("Phi4FlashConfig: the program alternates "
                             "Mamba and attention (mb_per_layer 2)")
        if self.n_layer % 4 or self.n_layer < 8:
            raise ValueError(
                f"invalid Phi4FlashConfig: n_layer {self.n_layer} must "
                f"be a multiple of 4, 8 or more (a self-decoder of "
                f"pairs, the memory and the full layer, then pairs)")
        if self.n_head % self.n_kv_head or self.n_kv_head % 2:
            raise ValueError(
                f"n_head {self.n_head} must divide by n_kv_head "
                f"{self.n_kv_head}, and K/V heads come in pairs")
        if self.d_model % self.n_head:
            raise ValueError("d_model must divide by n_head")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def kv_width(self) -> int:
        """One token's K (or V) of one layer, folded into one row."""
        return self.n_kv_head * self.head_dim

    @property
    def n_self(self) -> int:
        """(Mamba, window) pairs of the self-decoder: layers 0 .. half."""
        return self.n_layer // 4

    @property
    def n_cross(self) -> int:
        """(GMU, cross) pairs of the cross-decoder."""
        return self.n_layer // 4 - 1

    @property
    def n_mamba(self) -> int:
        return self.n_self + 1

    @property
    def n_kv_layer(self) -> int:
        """Layers whose K/V a pool of this model holds: one."""
        return 1

    def layer_index(self, kind: str) -> np.ndarray:
        """The model's layer indices of `kind`'s stack, in its order:
        ``window``, ``full`` (one) or ``cross``."""
        half = self.n_layer // 2
        return {"window": np.arange(1, half, 2), "full": np.array([half + 1]),
                "cross": np.arange(half + 3, self.n_layer, 2)}[kind]

    def lambda_init(self, kind: str) -> np.ndarray:
        """``0.8 - 0.6 exp(-0.3 i)`` of `kind`'s layers, float32."""
        return (0.8 - 0.6 * np.exp(-0.3 * self.layer_index(kind))
                ).astype(np.float32)

    @property
    def pairs(self):
        """The geometry the grouped-query walks see (module docstring):
        ``n_kv_head / 2`` K/V pair-heads of ``2 head_dim`` lanes."""
        return types.SimpleNamespace(
            n_kv_head=self.n_kv_head // 2, head_dim=2 * self.head_dim,
            dtype=self.dtype, attn_block=self.attn_block,
            scale=1.0 / math.sqrt(self.head_dim))

    @property
    def state_bytes_per_slot(self) -> int:
        """One sequence's SSM states and convolution windows and its
        window layers' rings."""
        di = self.d_inner
        mamba = self.n_mamba * (
            self.d_state * di * jnp.dtype(self.state_dtype).itemsize
            + (self.d_conv - 1) * di * jnp.dtype(self.dtype).itemsize)
        rings = self.n_self * self.window * 2 * self.kv_width \
            * jnp.dtype(self.dtype).itemsize
        return mamba + rings


_PRESETS: Dict[str, Dict[str, Any]] = {
    # the structure whole: three (Mamba, window) pairs, the memory layer,
    # the full layer, two (GMU, cross) pairs; 4 query pairs over 2 K/V
    # pair-heads; a window a short prompt wraps several times
    "nano": dict(vocab_size=512, max_seq=128, n_layer=12, n_head=8,
                 n_kv_head=4, d_model=64, d_ff=128, window=8, dt_rank=8,
                 scan_chunk=8, attn_block=16),
    "phi4-mini-flash": {},
}


def phi4flash_config(name: str = "phi4-mini-flash",
                     **overrides) -> Phi4FlashConfig:
    return Phi4FlashConfig(**dict(_PRESETS[name], **overrides))


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _mlp_and_norms(cfg: Phi4FlashConfig) -> int:
    return 3 * cfg.d_model * cfg.d_ff + 4 * cfg.d_model


def mixer_params(cfg: Phi4FlashConfig) -> Dict[str, int]:
    """One mixer's parameters, by kind."""
    d, di, N, K, R = (cfg.d_model, cfg.d_inner, cfg.d_state, cfg.d_conv,
                      cfg.dt_rank)
    hd = cfg.head_dim
    lam = 4 * hd + 2 * hd
    out = d * d + d
    return {"mamba": (d * 2 * di + K * di + di + di * (R + 2 * N)
                      + R * di + di + di * N + di + di * d),
            "self": d * (d + 2 * cfg.kv_width) + d + 2 * cfg.kv_width
            + out + lam,
            "gmu": 2 * d * di,
            "cross": d * d + d + out + lam}


def phi4flash_param_count(cfg: Phi4FlashConfig) -> int:
    m = mixer_params(cfg)
    return (cfg.vocab_size * cfg.d_model + 2 * cfg.d_model
            + cfg.n_layer * _mlp_and_norms(cfg)
            + cfg.n_mamba * m["mamba"] + (cfg.n_self + 1) * m["self"]
            + cfg.n_cross * (m["gmu"] + m["cross"]))


def _shared_axes(lead) -> Dict[str, Any]:
    ln = {"scale": lead + ("embed",), "bias": lead + ("embed",)}
    return {"ln1": dict(ln), "ln2": dict(ln),
            "mlp": {"w1": lead + ("embed", "mlp"),
                    "w2": lead + ("mlp", "embed")}}


def phi4flash_logical_axes(cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """Pytree (matching phi4flash_init's) of logical-axis tuples; the
    leading None on a stacked layer's leaves is its stack's axis."""
    def mamba(lead):
        return dict(_shared_axes(lead), mixer={
            "in_proj": lead + ("embed", "mlp"),
            "conv_w": lead + (None, "mlp"), "conv_b": lead + ("mlp",),
            "x_proj": lead + ("mlp", None),
            "dt_proj": lead + (None, "mlp"), "dt_bias": lead + ("mlp",),
            "A_log": lead + (None, "mlp"), "D": lead + ("mlp",),
            "out_proj": lead + ("mlp", "embed")})

    def lambdas(lead):
        return {n: lead + (None,) for n in ("lq1", "lk1", "lq2", "lk2",
                                            "subln")}

    def attn(lead, cross=False):
        p = dict(lambdas(lead), wq=lead + ("embed", None),
                 bq=lead + (None,), wo=lead + (None, "embed"),
                 bo=lead + ("embed",))
        if not cross:
            p.update(wkv=lead + ("embed", None), bkv=lead + (None,))
        return dict(_shared_axes(lead), attn=p)

    def gmu(lead):
        return dict(_shared_axes(lead), gmu={
            "w_in": lead + ("embed", "mlp"), "w_out": lead + ("mlp", "embed")})

    one = (None,)
    return {"wte": ("vocab", "embed"),
            "ln_f": {"scale": ("embed",), "bias": ("embed",)},
            "self": {"mamba": mamba(one), "window": attn(one)},
            "memory": mamba(()), "full": attn(()),
            "cross": {"gmu": gmu(one), "attn": attn(one, cross=True)}}


def phi4flash_init(key, cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """Seeded weights.  Projections N(0, 0.02), those into the residual
    scaled by 1/sqrt(2 n_layer), biases zero; the SSM as Mamba-1
    initialises it (jamba.jamba_init: ``A_log = log(1..d_state)``, ``D
    = 1``, ``dt_proj`` N(0, dt_rank^-1/2) with a bias whose softplus is
    log-uniform in [1e-3, 1e-1]); LayerNorms 1 and 0; the four lambda
    vectors N(0, 0.1) as published, so that ``lam`` is near
    ``lam_init`` and the second softmax weighs 0.2-0.8; the differential
    norm's weight 1.  Every tensor is drawn by a program of its own, so a
    float32 draw is never whole beside the weights."""
    d, f, di, N, K, R = (cfg.d_model, cfg.d_ff, cfg.d_inner, cfg.d_state,
                         cfg.d_conv, cfg.dt_rank)
    hd, kvw = cfg.head_dim, cfg.kv_width
    pd = cfg.param_dtype
    std = 0.02
    res_std = std / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 80))

    def normal(shape, s=std):
        return jax.jit(lambda k: (jax.random.normal(
            k, shape, jnp.float32) * s).astype(pd))(next(keys))

    def shared(lead):
        return {"ln1": {"scale": jnp.ones(lead + (d,), pd),
                        "bias": jnp.zeros(lead + (d,), pd)},
                "ln2": {"scale": jnp.ones(lead + (d,), pd),
                        "bias": jnp.zeros(lead + (d,), pd)},
                "mlp": {"w1": normal(lead + (d, 2 * f)),
                        "w2": normal(lead + (f, d), res_std)}}

    def mamba(lead):
        dt = jnp.exp(jax.random.uniform(next(keys), lead + (di,),
                                        jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return dict(shared(lead), mixer={
            "in_proj": normal(lead + (d, 2 * di)),
            "conv_w": normal(lead + (K, di), 1.0 / math.sqrt(K)),
            "conv_b": normal(lead + (di,)),
            "x_proj": normal(lead + (di, R + 2 * N)),
            "dt_proj": normal(lead + (R, di), R ** -0.5),
            # softplus^-1(dt)
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[:, None],
                lead + (N, di)).astype(pd),
            "D": jnp.ones(lead + (di,), pd),
            "out_proj": normal(lead + (di, d), res_std)})

    def attn(lead, cross=False):
        p = {"wq": normal(lead + (d, d)), "bq": jnp.zeros(lead + (d,), pd),
             "wo": normal(lead + (d, d), res_std),
             "bo": jnp.zeros(lead + (d,), pd),
             "subln": jnp.ones(lead + (2 * hd,), pd)}
        for name in ("lq1", "lk1", "lq2", "lk2"):
            p[name] = normal(lead + (hd,), 0.1)
        if not cross:
            p["wkv"] = normal(lead + (d, 2 * kvw))
            p["bkv"] = jnp.zeros(lead + (2 * kvw,), pd)
        return dict(shared(lead), attn=p)

    def gmu(lead):
        return dict(shared(lead), gmu={
            "w_in": normal(lead + (d, di)),
            "w_out": normal(lead + (di, d), res_std)})

    S, C = (cfg.n_self,), (cfg.n_cross,)
    return {"wte": normal((cfg.padded_vocab, d)),
            "ln_f": {"scale": jnp.ones((d,), pd),
                     "bias": jnp.zeros((d,), pd)},
            "self": {"mamba": mamba(S), "window": attn(S)},
            "memory": mamba(()), "full": attn(()),
            "cross": {"gmu": gmu(C), "attn": attn(C, cross=True)}}


# ---------------------------------------------------------------------------
# the parts of a layer
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.EMBED)
def embed(params, tokens, cfg: Phi4FlashConfig):
    """The residual stream's first value: the tokens' embeddings."""
    return params["wte"].astype(cfg.dtype)[tokens]


@jax.named_scope(scopes.LN)
def layernorm(x, p, cfg: Phi4FlashConfig):
    return layers.layernorm(x, p["scale"].astype(jnp.float32),
                            p["bias"].astype(jnp.float32), cfg.ln_eps)


@jax.named_scope(scopes.MLP)
def swiglu(x, p, cfg: Phi4FlashConfig):
    ga = x.astype(cfg.dtype) @ p["w1"].astype(cfg.dtype)
    gate, up = ga[..., :cfg.d_ff], ga[..., cfg.d_ff:]
    return ((jax.nn.silu(gate) * up)
            @ p["w2"].astype(cfg.dtype)).astype(x.dtype)


def mlp_residual(x, p, cfg: Phi4FlashConfig):
    """``x + MLP(LN(x))``: the second half of every layer."""
    return x + swiglu(layernorm(x, p["ln2"], cfg), p["mlp"], cfg)


@jax.named_scope(scopes.LM_HEAD)
def lm_logits(x, params, cfg: Phi4FlashConfig):
    """Float32 logits of ``LN_f(x)`` through the tied embedding."""
    x = layers.layernorm(x, params["ln_f"]["scale"].astype(jnp.float32),
                         params["ln_f"]["bias"].astype(jnp.float32),
                         cfg.ln_eps)
    return jnp.einsum("...d,vd->...v", x.astype(cfg.dtype),
                      params["wte"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def pair_queries(q, cfg: Phi4FlashConfig):
    """Queries (..., n_head * hd) as the walks take them: (..., n_head,
    2 hd), sub-head ``2p`` is ``[q_2p, 0]`` and ``2p + 1`` is ``[0,
    q_2p+1]``, so that against a K/V pair-head ``[k1 k2]`` each scores
    its own half."""
    hd = cfg.head_dim
    q = q.reshape(*q.shape[:-1], cfg.n_head // 2, 2, hd)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack(
        [jnp.concatenate([q[..., 0, :], zero], axis=-1),
         jnp.concatenate([zero, q[..., 1, :]], axis=-1)],
        axis=-2).reshape(*q.shape[:-3], cfg.n_head, 2 * hd)


def project_q(u, p, cfg: Phi4FlashConfig):
    """Normed input u (..., d) -> the layer's padded queries."""
    dt = cfg.dtype
    q = u.astype(dt) @ p["wq"].astype(dt) + p["bq"].astype(dt)
    return pair_queries(q, cfg)


def project_kv(u, p, cfg: Phi4FlashConfig):
    """u (..., d) -> k, v (..., kv_width) folded as they fall: head j at
    lanes ``[j hd, (j + 1) hd)``, so a pair-head is 2 hd lanes whole."""
    dt = cfg.dtype
    kv = u.astype(dt) @ p["wkv"].astype(dt) + p["bkv"].astype(dt)
    return kv[..., :cfg.kv_width], kv[..., cfg.kv_width:]


def diff_out(o, p, lam_init, cfg: Phi4FlashConfig):
    """The two softmaxes' outputs o (..., n_head, 2 hd), sub-heads (2p,
    2p+1) a pair, to the residual's width: ``(1 - lam_init) RMSNorm(o1
    - lam o2)`` a pair, then ``W_o`` and its bias.  float32 until the
    product."""
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lq2"].astype(f32) * p["lk2"].astype(f32)))
           + lam_init)
    o = o.astype(f32).reshape(*o.shape[:-2], cfg.n_head // 2, 2, -1)
    mixed = plain_rmsnorm(o[..., 0, :] - lam * o[..., 1, :],
                     p["subln"].astype(f32), cfg.ln_eps) * (1.0 - lam_init)
    mixed = mixed.reshape(*mixed.shape[:-2], cfg.d_model).astype(cfg.dtype)
    return mixed @ p["wo"].astype(cfg.dtype) + p["bo"].astype(cfg.dtype)


def attend_masked(q, k, v, mask, cfg: Phi4FlashConfig):
    """Padded queries q (B, T, n_head, 2 hd) over folded k, v (B, S,
    kv_width) under mask (B, T, S): banded_attention.py's
    whole-score-matrix grouped-query attention at the pair-head
    geometry.  The full-sequence forward and the dense cache's prefill,
    small sizes.
    (B, T, n_head, 2 hd)."""
    return banded_attention.attend_masked(q, k, v, mask, cfg.pairs,
                                          cfg.pairs.scale)


def mamba_layer(x, p, cfg: Phi4FlashConfig, window, state, real=None,
                capture=None):
    """One Mamba layer with its MLP on x (B, T, d): mamba.mamba_mix's
    contract.  Returns (x, (window, state), snapshot or None, m): what
    the layer would hand out as the memory (B, T, d_inner), in the
    compute dtype; only the last Mamba layer's is used."""
    out, state, snap, y = mamba_mix(
        p["mixer"], layernorm(x, p["ln1"], cfg), cfg, window, state,
        real=real, capture=capture)
    return mlp_residual(x + out, p, cfg), state, snap, y.astype(cfg.dtype)


def attn_layer(x, p, lam_init, cfg: Phi4FlashConfig, scope: str,
               attend: Callable):
    """One self-attention layer (window or full: `scope`) with its MLP.
    ``attend(q, k, v) -> o`` is the caller's: it owns the cache, sees
    the padded queries and this layer's new rows, folded, and returns
    the sub-heads' outputs (..., n_head, 2 hd)."""
    u = layernorm(x, p["ln1"], cfg)
    with jax.named_scope(scope):
        q = project_q(u, p["attn"], cfg)
        k, v = project_kv(u, p["attn"], cfg)
    o = attend(q, k, v)
    with jax.named_scope(scope):
        x = x + diff_out(o, p["attn"], lam_init, cfg).astype(x.dtype)
    return mlp_residual(x, p, cfg)


def gmu_layer(x, p, m, cfg: Phi4FlashConfig):
    """One Gated Memory Unit layer with its MLP: x (..., d), m (...,
    d_inner) the same positions' memory."""
    u = layernorm(x, p["ln1"], cfg)
    with jax.named_scope(scopes.GMU):
        dt = cfg.dtype
        gate = jax.nn.silu((u.astype(dt) @ p["gmu"]["w_in"].astype(dt)
                            ).astype(jnp.float32))
        out = (gate * m.astype(jnp.float32)).astype(dt) \
            @ p["gmu"]["w_out"].astype(dt)
    return mlp_residual(x + out.astype(x.dtype), p, cfg)


def cross_layer(x, p, lam_init, cfg: Phi4FlashConfig, attend: Callable):
    """One cross-attention layer with its MLP: its own queries over the
    full layer's K/V, which ``attend(q) -> o`` holds."""
    u = layernorm(x, p["ln1"], cfg)
    with jax.named_scope(scopes.ATTN_CROSS):
        q = project_q(u, p["attn"], cfg)
        o = attend(q)
        x = x + diff_out(o, p["attn"], lam_init, cfg).astype(x.dtype)
    return mlp_residual(x, p, cfg)


def cross_decoder(params, x, m, cfg: Phi4FlashConfig, attend: Callable):
    """`x` (..., d) through the (GMU, cross) pairs with the memory `m`
    (..., d_inner) of the same positions; ``attend(q) -> o`` reads the
    full layer's K/V for every cross layer alike."""
    def pair(x, xs):
        p, lam_init = xs
        x = gmu_layer(x, p["gmu"], m, cfg)
        return cross_layer(x, p["attn"], lam_init, cfg, attend), None

    with jax.named_scope(scopes.LAYER_SCAN):
        x, _ = lax.scan(pair, x, (params["cross"],
                                  jnp.asarray(cfg.lambda_init("cross"))))
    return x


def zero_recurrent(cfg: Phi4FlashConfig, batch: int):
    """(window, state) of zeros, stacked over the Mamba layers: a
    sequence that has seen nothing."""
    return (jnp.zeros((cfg.n_mamba, cfg.d_conv - 1, batch, cfg.d_inner),
                      cfg.dtype),
            jnp.zeros((cfg.n_mamba, batch, cfg.d_state, cfg.d_inner),
                      cfg.state_dtype))


def causal_mask(T: int, window: Optional[int] = None):
    """(T, T) bool: what position i attends, of everything before it or
    of the last `window` positions."""
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    mask = j <= i
    return mask if window is None else mask & (j > i - window)


# ---------------------------------------------------------------------------
# full-sequence forward and loss
# ---------------------------------------------------------------------------

def phi4flash_hidden(params, tokens, cfg: Phi4FlashConfig,
                     rules=DEFAULT_RULES):
    """tokens (B, T) -> the last layer's residual (B, T, d): every layer
    over every position, every sequence from a zero state, no cache."""
    B, T = tokens.shape
    x = embed(params, tokens, cfg)
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    window, state = (a[0] for a in zero_recurrent(cfg, B))
    band = causal_mask(T, cfg.window)[None]
    causal = causal_mask(T)[None]

    def masked(mask, scope):
        def attend(q, k, v):
            with jax.named_scope(scope):
                return attend_masked(q, k, v, mask, cfg)
        return attend

    def pair(x, xs):
        p, lam_init = xs
        x = mamba_layer(x, p["mamba"], cfg, window, state)[0]
        x = attn_layer(x, p["window"], lam_init, cfg, scopes.ATTN_WINDOW,
                       masked(band, scopes.ATTN_WINDOW))
        return with_logical_constraint(
            x, ("batch", "seq", "embed"), rules), None

    with jax.named_scope(scopes.LAYER_SCAN):
        x, _ = lax.scan(pair, x, (params["self"],
                                  jnp.asarray(cfg.lambda_init("window"))))
    x, _, _, m = mamba_layer(x, params["memory"], cfg, window, state)
    held = {}

    def full(q, k, v):
        held.update(k=k, v=v)
        with jax.named_scope(scopes.ATTN_FULL):
            return attend_masked(q, k, v, causal, cfg)

    x = attn_layer(x, params["full"], cfg.lambda_init("full")[0], cfg,
                   scopes.ATTN_FULL, full)
    return cross_decoder(
        params, x, m, cfg,
        lambda q: attend_masked(q, held["k"], held["v"], causal, cfg))


def phi4flash_forward(params, tokens, cfg: Phi4FlashConfig,
                      rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    logits = lm_logits(phi4flash_hidden(params, tokens, cfg, rules),
                       params, cfg)
    return with_logical_constraint(logits, ("batch", "seq", "vocab"),
                                   rules)


def phi4flash_loss(params, batch, cfg: Phi4FlashConfig,
                   rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}, optionally {"mask"}.  Nothing here trains the
    model at its published size: the scan kernel has no backward pass
    (the ``jnp`` chain differentiates, off the chip)."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    nll = nll_from_logits(phi4flash_forward(params, inputs, cfg, rules),
                          targets, cfg.vocab_size, cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


__all__ = ["Phi4FlashConfig", "phi4flash_config", "phi4flash_init",
           "phi4flash_forward", "phi4flash_loss", "phi4flash_logical_axes",
           "phi4flash_param_count", "phi4flash_hidden"]
