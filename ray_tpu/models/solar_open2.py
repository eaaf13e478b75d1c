"""Solar-Open2 family decoder (``model_type: solar_open2``): three
linear-attention layers (Kimi Delta Attention, a gated delta rule whose
state is a float32 matrix a head) to one gated softmax layer without
positions, every layer ending in a sparse expert layer.

Same template as gpt2.py / llama.py / jamba.py / kimi_k2.py / laguna.py
(pure init/apply over pytrees, logical axes, bf16 compute over float32
or bf16 weights).  Layer ``i`` is softmax attention (`GQA`) iff ``i`` is
in ``cfg.gqa_layers``, else `KDA`; the layers are a LIST
(``params["layers"]``) walked unrolled, as laguna.py's are: two kinds
of different shapes in one period of four do not stack.

The layer equations, ``u = RMSNorm(h)``, no bias anywhere, ``h <- h +
mixer(u)``, then ``h <- h + FFN(RMSNorm(h))``.  NO positions anywhere
(``use_rope: false``): a softmax layer orders tokens by its causal mask
alone, a KDA layer by its recurrence.

  * GQA.  ``q = u W_q`` (``n_head`` heads of ``head_dim``), ``k = u
    W_k``, ``v = u W_v`` (``n_kv_head`` heads; query head ``h`` reads
    K/V head ``h // (n_head / n_kv_head)``), K and V of a token folded
    into one row of ``kv_width`` lanes as banded_attention.py reads them.
    ``score = q.k / sqrt(head_dim)`` over ``j <= i``, float32 softmax;
    ``a = concat_h(g_h * o_h) W_o`` with ``g = sigmoid(u W_g)``, one
    value a CHANNEL of each head (`banded_attention.attn_out`, whose
    gate may also be one a head).
  * KDA, ``kda_heads`` heads with keys and values of ``kda_head_dim``
    (ops/kda.py has the recurrence and its chunked form):
    ``[q~ | k~ | v] = SiLU(conv(u W_qkv))``, three causal depthwise
    convolutions of kernel ``d_conv`` (one over the 3 x heads x
    head_dim channels; a slot keeps the last ``d_conv - 1`` inputs, the
    WINDOW); ``q = q~ / |q~| * head_dim^-1/2``, ``k = k~ / |k~|`` per
    head (eps 1e-6).  Per-channel log-decay ``g = -exp(A_log_h) *
    softplus(u W_fa W_fb + dt_bias)`` (a low-rank pair of rank
    ``gate_rank``); ``beta = sigmoid(u W_beta)``, one a head, doubled
    where ``neg_eigval``.  State ``S`` (head_dim, head_dim) float32 a
    head: ``S' = Diag(exp(g_t)) S``; ``S_t = S' + beta_t k_t (v_t -
    S'^T k_t)^T``; ``o_t = S_t^T q_t``.  ``out = concat_h(RMSNorm_h(o)
    * sigmoid(u W_ga W_gb)) W_o``, the norm over each head's values with
    a learned weight of ``head_dim``.
  * FFN, every layer: `experts.moe_layer` on ``m = RMSNorm(h)`` in
    float32: sigmoid scores over all ``n_routed`` experts, top-k by
    score + a selection bias that does not weigh, renormalised, times
    ``route_scale``; ``held`` says which experts this chip has; one
    shared expert summed ungated.
  * logits ``= RMSNorm(h) W_head^T``: the head is NOT tied.

A pad is an identity step of the recurrence (``beta = 0``, ``g = 0``)
and leaves the window alone, as jamba.py's pads do (`kda_mix`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import scopes
from ray_tpu.models import experts as ex
# the grouped-query attention over folded K/V and the gated output
# projection, the norm, the embedding lookup and the untied head read
# `dtype`, `rms_eps`, `n_kv_head` and `d_model` off whichever config
# they are handed
from ray_tpu.models.banded_attention import attend_masked, attn_out
from ray_tpu.models.layers import (DECAY_SPAN, EMBED_STD, SILU_IN, embed,
                                   lm_logits, nll_from_logits,
                                   plain_rmsnorm, rmsnorm, unit)
from ray_tpu.models.mamba import conv_inputs
from ray_tpu.ops.kda import kda_decode, kda_prefill
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)

GQA, KDA = "gqa", "kda"


@dataclasses.dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196_608
    max_seq: int = 4096
    n_layer: int = 48
    #: the layers that are softmax attention, as the source lists them;
    #: those at or past ``n_layer`` name nothing
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    d_model: int = 4096
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    kda_heads: int = 64
    kda_head_dim: int = 128
    d_conv: int = 4
    #: rank of the low-rank pairs of the decay and of the output gate
    gate_rank: int = 128
    #: ``beta`` in (0, 2): the state's transition may reflect
    neg_eigval: bool = True
    d_expert: int = 1280
    n_routed: int = 320
    #: which of the n_routed experts this chip holds (experts.py); None
    #: holds them all
    held: Optional[Tuple[int, ...]] = None
    top_k: int = 8
    n_shared: int = 1
    scoring: str = "sigmoid"
    norm_topk: bool = True
    route_scale: float = 1.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_pad_to: int = 128
    #: queries and keys a tile of the prefill's banded attention
    attn_block: int = 512
    #: tokens a step of the prefill's chunked delta rule (ops/kda.py)
    kda_chunk: int = 64
    #: most rows of grouped assignments one pass of the experts takes:
    #: an 8k prefill's share of 8,192 x 8 x 40/320 and four deviations
    #: more in ONE pass (a multiple of 256, `experts.tile_rows`); 0.14
    #: GB of float32 rows each way
    moe_tile_rows: int = 8704
    #: taken and not read: the harness's rehearsal lays it over every
    #: family's overrides, and this family has one attention path
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head {self.n_head} must be a multiple of "
                             f"n_kv_head={self.n_kv_head}")
        if self.d_conv < 2:
            raise ValueError("d_conv must be at least 2")
        self.experts  # its own checks

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(GQA if i in self.gqa_layers else KDA
                     for i in range(self.n_layer))

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The indices of the layers whose mixer is `kind`."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == kind)

    @property
    def kv_width(self) -> int:
        """One token's K (or V) of one GQA layer, folded into one row."""
        return self.n_kv_head * self.head_dim

    @property
    def kda_width(self) -> int:
        """The channels of ONE of a KDA layer's q, k and v."""
        return self.kda_heads * self.kda_head_dim

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p

    @property
    def experts(self) -> ex.ExpertsConfig:
        return ex.ExpertsConfig(
            d_model=self.d_model, d_expert=self.d_expert,
            n_routed=self.n_routed, top_k=self.top_k, held=self.held,
            scoring=self.scoring, norm_topk=self.norm_topk,
            route_scale=self.route_scale, n_shared=self.n_shared,
            dtype=self.dtype, param_dtype=self.param_dtype,
            tile_rows=self.moe_tile_rows)


_PRESETS: Dict[str, Dict[str, Any]] = {
    # one period and a layer: softmax, three KDA, softmax; 4 query
    # heads over 2 K/V heads, 4 KDA heads of 16, 16 experts of which a
    # token takes 4
    "nano": dict(vocab_size=512, max_seq=128, n_layer=5,
                 gqa_layers=(0, 4), d_model=64, n_head=4, n_kv_head=2,
                 head_dim=16, kda_heads=4, kda_head_dim=16, gate_rank=8,
                 d_expert=32, n_routed=16, top_k=4, attn_block=16,
                 kda_chunk=16, moe_tile_rows=4096),
    # the published config.json, whole
    "solar-open2": {},
}


def solar_open2_config(name: str = "solar-open2",
                       **overrides) -> SolarOpen2Config:
    """`overrides` may give ``held`` and ``gqa_layers`` as any
    sequences."""
    kw = dict(_PRESETS[name], **overrides)
    for key in ("held", "gqa_layers"):
        if kw.get(key) is not None:
            kw[key] = tuple(int(e) for e in kw[key])
    return SolarOpen2Config(**kw)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def gqa_params(cfg: SolarOpen2Config) -> int:
    """q, o and the per-channel gate; k and v."""
    d = cfg.d_model
    return 3 * d * cfg.n_head * cfg.head_dim + 2 * d * cfg.kv_width


def kda_params(cfg: SolarOpen2Config) -> int:
    """q, k, v and o; the three convolutions; the two low-rank pairs;
    ``W_beta``, ``A_log``, ``dt_bias`` and the output norm."""
    d, w, r = cfg.d_model, cfg.kda_width, cfg.gate_rank
    return (4 * d * w + 3 * cfg.d_conv * w + 2 * (d * r + r * w)
            + d * cfg.kda_heads + cfg.kda_heads + w + cfg.kda_head_dim)


def solar_open2_param_count(cfg: SolarOpen2Config) -> int:
    """Embedding and head (untied), the final norm, and per layer its
    mixer, two norms and its expert layer (the experts it HOLDS)."""
    d = cfg.d_model
    mixers = {GQA: gqa_params(cfg), KDA: kda_params(cfg)}
    return (2 * cfg.vocab_size * d + d
            + sum(mixers[t] for t in cfg.layer_types)
            + cfg.n_layer * (2 * d + ex.experts_param_count(cfg.experts)))


_MIXER_AXES = {
    GQA: ("attn", {"wq": ("embed", "heads", "head_dim"),
                   "wk": ("embed", None), "wv": ("embed", None),
                   "wg": ("embed", "heads", "head_dim"),
                   "wo": ("heads", "head_dim", "embed")}),
    KDA: ("kda", {"wqkv": ("embed", None, "heads", "head_dim"),
                  "conv_w": (None, None, "heads", "head_dim"),
                  "wf_a": ("embed", None),
                  "wf_b": (None, "heads", "head_dim"),
                  "dt_bias": ("heads", "head_dim"), "A_log": ("heads",),
                  "wb": ("embed", "heads"), "wg_a": ("embed", None),
                  "wg_b": (None, "heads", "head_dim"),
                  "o_norm": ("head_dim",),
                  "wo": ("heads", "head_dim", "embed")}),
}


def solar_open2_logical_axes(cfg: SolarOpen2Config) -> Dict[str, Any]:
    """Pytree (matching solar_open2_init's) of logical-axis tuples."""
    def layer(kind):
        name, axes = _MIXER_AXES[kind]
        return {"ln1": {"scale": ("embed",)}, "ln2": {"scale": ("embed",)},
                name: dict(axes), "moe": ex.experts_logical_axes(cfg.experts)}

    return {"wte": ("vocab", "embed"), "head": ("vocab", "embed"),
            "ln_f": {"scale": ("embed",)},
            "layers": [layer(t) for t in cfg.layer_types]}


@functools.partial(jax.jit, static_argnames=("shape", "std", "dtype"))
def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def solar_open2_init(key, cfg: SolarOpen2Config) -> Dict[str, Any]:
    """Seeded weights: projections N(0, 0.02), those into the residual
    stream scaled by 1/sqrt(2 n_layer), norms 1; the router float32 and
    its selection bias small (experts.experts_init, as kimi_k2.py's).
    The embedding N(0, `EMBED_STD`) and the convolutions' taps such
    that a SiLU's input has the deviation `SILU_IN`, both so that what
    a router scores is the TOKEN'S (see the two constants): training leaves the experts' load even, and a
    seeded model has to get there by its draw.

    ``A_log`` and ``dt_bias`` are drawn so that a channel's per-token
    decay ``exp(-exp(A_log) softplus(dt_bias))`` spans `DECAY_SPAN`,
    its rate ``-ln(decay)`` log-uniform between the two ends (the
    low-rank pair's N(0, 0.02) products move a token's rate by about a
    third of itself at the published widths): ``A_log`` 0 and
    ``dt_bias`` the inverse softplus of the wanted rate.  A state that
    forgets in ten tokens tests nothing at 8k.  Every tensor is drawn
    by a program of its own (one a shape), so a float32 draw is never
    whole beside the weights."""
    d, hd = cfg.d_model, cfg.head_dim
    H, kd, r = cfg.kda_heads, cfg.kda_head_dim, cfg.gate_rank
    pd = cfg.param_dtype
    std = 0.02
    res_std = std / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 3 + 12 * cfg.n_layer))
    experts = jax.jit(lambda k: ex.experts_init(
        k, cfg.experts, std=std, out_std=res_std))

    def normal(shape, s=std):
        return _normal(next(keys), shape, s, pd)

    def dt_bias():
        lo, hi = (-math.log(x) for x in DECAY_SPAN)    # rates, hi < lo
        u = jax.random.uniform(next(keys), (H, kd), jnp.float32)
        rate = jnp.exp(math.log(hi) + u * (math.log(lo) - math.log(hi)))
        return jnp.log(jnp.expm1(rate)).astype(pd)     # softplus^-1

    def mixer(kind):
        if kind == GQA:
            return "attn", {"wq": normal((d, cfg.n_head, hd)),
                            "wk": normal((d, cfg.kv_width)),
                            "wv": normal((d, cfg.kv_width)),
                            "wg": normal((d, cfg.n_head, hd)),
                            "wo": normal((cfg.n_head, hd, d), res_std)}
        return "kda", {"wqkv": normal((d, 3, H, kd)),
                       "conv_w": normal((cfg.d_conv, 3, H, kd), SILU_IN / (
                           std * math.sqrt(d * cfg.d_conv))),
                       "wf_a": normal((d, r)), "wf_b": normal((r, H, kd)),
                       "dt_bias": dt_bias(),
                       "A_log": jnp.zeros((H,), pd),
                       "wb": normal((d, H)),
                       "wg_a": normal((d, r)), "wg_b": normal((r, H, kd)),
                       "o_norm": jnp.ones((kd,), pd),
                       "wo": normal((H, kd, d), res_std)}

    def layer(kind):
        name, weights = mixer(kind)
        return {"ln1": {"scale": jnp.ones((d,), pd)},
                "ln2": {"scale": jnp.ones((d,), pd)}, name: weights,
                "moe": experts(next(keys))}

    return {"wte": normal((cfg.padded_vocab, d), EMBED_STD),
            "head": normal((cfg.padded_vocab, d)),
            "ln_f": {"scale": jnp.ones((d,), pd)},
            "layers": [layer(t) for t in cfg.layer_types]}


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.ATTN_FULL)
def gqa_project(u, p, cfg: SolarOpen2Config):
    """u (..., d) normed input -> q (..., H, hd), k and v (..., kv_width)
    folded, gate (..., H, hd) float32 in (0, 1).  No rotary."""
    dt = cfg.dtype
    u = u.astype(dt)
    heads = (*u.shape[:-1], cfg.n_head, cfg.head_dim)
    q = (u @ p["wq"].astype(dt).reshape(cfg.d_model, -1)).reshape(heads)
    gate = jax.nn.sigmoid((u @ p["wg"].astype(dt).reshape(
        cfg.d_model, -1)).astype(jnp.float32)).reshape(heads)
    return q, u @ p["wk"].astype(dt), u @ p["wv"].astype(dt), gate


def zero_recurrent(cfg: SolarOpen2Config, batch: int, layers: bool = True):
    """(window, state) of zeros: a sequence that has seen nothing.  The
    window (d_conv - 1, batch, 3 x kda_width), compute dtype; the state
    (batch, heads, head_dim, head_dim), float32.  With `layers`, stacked
    over the KDA layers on a leading axis."""
    lead = (len(cfg.layers_of(KDA)),) if layers else ()
    return (jnp.zeros(lead + (cfg.d_conv - 1, batch, 3 * cfg.kda_width),
                      cfg.dtype),
            jnp.zeros(lead + (batch, cfg.kda_heads, cfg.kda_head_dim,
                              cfg.kda_head_dim), jnp.float32))


@jax.named_scope(scopes.ATTN_LINEAR)
def kda_mix(p, u, cfg: SolarOpen2Config, window, state, real=None,
            capture=None, layer=None):
    """The KDA mixer on normalised input u (B, T, d).

    window (d_conv-1, B, 3 x kda_width): the convolutions' last inputs,
    compute dtype; state (B, H, hd, hd) float32 or, with `layer` (one
    column only), the KDA layers' stack (n_kda, B, H, hd, hd) of which
    this layer's is entry `layer`: a decode wave hands the whole stack
    over and it is updated where it lies.  real (B, T) bool marks
    the columns that hold a token: a row's pads come first, its tokens
    after them (left padding), and a pad moves neither window nor state.
    capture: a traced column index (rows all alike) after which window
    and state are also handed back, for a snapshot.  One column (a
    decode wave) goes through `kda_decode` (the kernel of that name on
    the chip, `kda_step` elsewhere), more through `kda_prefill` (the
    kernel `kda_chunk` on the chip, the `jnp` scan elsewhere).

    Returns (out (B, T, d), (window, state as it came: a layer's or the
    stack), (window, state) after `capture` or None)."""
    B, T, _ = u.shape
    H, hd, K = cfg.kda_heads, cfg.kda_head_dim, cfg.d_conv
    dt, f32 = cfg.dtype, jnp.float32
    u = u.astype(dt)

    def low_rank(a, b):
        return jnp.einsum("btr,rhd->bthd", u @ p[a].astype(dt),
                          p[b].astype(dt), preferred_element_type=f32)

    x = u @ p["wqkv"].astype(dt).reshape(cfg.d_model, -1)
    if real is not None:
        x = jnp.where(real[..., None], x, jnp.zeros((), x.dtype))
    ext = conv_inputs(x, window, real)
    w = p["conv_w"].astype(f32).reshape(K, -1)
    qkv = jax.nn.silu(sum(ext[:, i:i + T].astype(f32) * w[i]
                          for i in range(K))).reshape(B, T, 3, H, hd)
    q = unit(qkv[:, :, 0]) * hd ** -0.5
    k, v = unit(qkv[:, :, 1]), qkv[:, :, 2]
    g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
        low_rank("wf_a", "wf_b") + p["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(jnp.einsum(
        "btd,dh->bth", u, p["wb"].astype(dt), preferred_element_type=f32))
    if cfg.neg_eigval:
        beta = 2.0 * beta
    if real is not None:
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    snap_state = None
    if T == 1:
        # a layer's state alone is a stack of one
        stack, j = (state[None], 0) if layer is None else (state, layer)
        o, stack = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], stack, j)
        o, new_state = o[:, None], stack[0] if layer is None else stack
    else:
        o, new_state, snap_state = kda_prefill(
            q, k, v, g, beta, state, chunk=cfg.kda_chunk, dtype=dt,
            capture=capture)
    o = plain_rmsnorm(o, p["o_norm"], cfg.rms_eps) \
        * jax.nn.sigmoid(low_rank("wg_a", "wg_b"))
    out = o.astype(dt).reshape(B, T, -1) @ p["wo"].astype(dt).reshape(
        -1, cfg.d_model)
    snap = None
    if capture is not None:
        snap = (jax.lax.dynamic_slice_in_dim(
            ext, capture + 1, K - 1, axis=1).swapaxes(0, 1).astype(
                window.dtype), snap_state)
    return (out.astype(u.dtype),
            (ext[:, T:].swapaxes(0, 1).astype(window.dtype), new_state),
            snap)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def ffn(x, p, cfg: SolarOpen2Config, valid=None, tiled: bool = True):
    """``x + Experts(RMSNorm(x))`` on x (..., d); `valid` (...) marks
    the rows that hold a token (experts.routed_experts).  The router
    reads the norm in float32, not through the stream's bf16.  Returns
    (x, experts.STATS of the layer)."""
    d = x.shape[-1]
    m = rmsnorm(x.astype(jnp.float32), p["ln2"]["scale"], cfg.rms_eps)
    y, stats = ex.moe_layer(
        p["moe"], m.reshape(-1, d), cfg.experts,
        None if valid is None else valid.reshape(-1), tiled)
    return x + y.reshape(x.shape).astype(x.dtype), stats


def gqa_mixer(x, p, cfg: SolarOpen2Config, attend: Callable):
    """``x + Attention(RMSNorm(x))`` of one softmax layer on x (..., d).
    ``attend(q, k, v) -> o (..., H, hd)`` is the caller's: it owns the
    cache (and sees this layer's new rows, folded)."""
    q, k, v, gate = gqa_project(
        rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps), p["attn"], cfg)
    o = attend(q, k, v)
    with jax.named_scope(scopes.ATTN_FULL):
        return x + attn_out(o, gate, p["attn"], cfg).astype(x.dtype)


def gqa_block(x, p, cfg: SolarOpen2Config, attend: Callable, valid=None,
              tiled: bool = True):
    """One softmax layer on x (..., d): `gqa_mixer`, then `ffn`.
    Returns (x, the expert layer's stats)."""
    return ffn(gqa_mixer(x, p, cfg, attend), p, cfg, valid, tiled)


def kda_block(x, p, cfg: SolarOpen2Config, window, state, real=None,
              capture=None, tiled: bool = True, layer=None):
    """One KDA layer on x (B, T, d) from (`window`, `state`), `kda_mix`'s
    arguments.  Returns (x, the expert layer's stats, (window, state)
    after the last column, the same after `capture` or None)."""
    out, after, snap = kda_mix(
        p["kda"], rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps), cfg, window,
        state, real, capture, layer)
    x, stats = ffn(x + out, p, cfg, real, tiled)
    return x, stats, after, snap


def walk_layers(cfg: SolarOpen2Config, params, x, layer: Callable):
    """`x` through the layers, unrolled.  ``layer(x, p, kind, j) -> (x,
    stats)`` is a layer with weights `p`, the `j`-th of its kind.
    Returns (x, the layers' expert stats (n_layer, len(STATS)))."""
    seen = {GQA: 0, KDA: 0}
    stats: List[Any] = []
    for p, kind in zip(params["layers"], cfg.layer_types):
        x, s = layer(x, p, kind, seen[kind])
        seen[kind] += 1
        stats.append(s)
    return x, jnp.stack(stats)


def solar_open2_hidden(params, tokens, cfg: SolarOpen2Config,
                       rules=DEFAULT_RULES):
    """tokens (B, T) -> final hidden (B, T, d): the full-sequence
    forward, no cache, every sequence from a zero state.  Every sorted
    assignment goes through one grouped matmul (``tiled=False``)."""
    B, T = tokens.shape
    mask = jnp.tril(jnp.ones((T, T), bool))[None]
    window, state = zero_recurrent(cfg, B, layers=False)
    x = with_logical_constraint(embed(params, tokens, cfg),
                                ("batch", "seq", "embed"), rules)

    def layer(x, p, kind, j):
        if kind == GQA:
            def attend(q, k, v):
                with jax.named_scope(scopes.ATTN_FULL):
                    return attend_masked(q, k, v, mask, cfg)

            x, stats = gqa_block(x, p, cfg, attend, tiled=False)
        else:
            x, stats, _, _ = kda_block(x, p, cfg, window, state,
                                       tiled=False)
        return with_logical_constraint(x, ("batch", "seq", "embed"),
                                       rules), stats

    return walk_layers(cfg, params, x, layer)[0]


def solar_open2_forward(params, tokens, cfg: SolarOpen2Config,
                        rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    hidden = solar_open2_hidden(params, tokens, cfg, rules)
    return with_logical_constraint(lm_logits(hidden, params, cfg),
                                   ("batch", "seq", "vocab"), rules)


def solar_open2_loss(params, batch, cfg: SolarOpen2Config,
                     rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}, optionally {"mask"}.  A forward's number:
    nothing here trains an expert layer, and the chunked delta rule has
    no backward of its own."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    nll = nll_from_logits(solar_open2_forward(params, inputs, cfg, rules),
                          targets, cfg.vocab_size, cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


__all__ = ["SolarOpen2Config", "solar_open2_config", "solar_open2_init",
           "solar_open2_forward", "solar_open2_loss",
           "solar_open2_logical_axes", "solar_open2_param_count", "GQA",
           "KDA"]
