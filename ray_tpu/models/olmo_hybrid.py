"""Olmo-Hybrid family decoder (``model_type: olmo_hybrid``): three
linear-attention layers (Gated DeltaNet, a delta rule with ONE decay a
head whose state is a float32 matrix a head, keys of 96 by values of
192) to one full multi-head softmax layer without positions, every
layer ending in a dense SwiGLU, every sublayer normed on its OUTPUT.

Same template as solar_open2.py (pure init/apply over pytrees, logical
axes, bf16 compute over float32 or bf16 weights; the layers a LIST
walked unrolled: two kinds of different shapes in one period of four do
not stack).  Layer ``i`` is ``cfg.layer_types[i]``, `LINEAR` or `FULL`,
the source's own words.

The layer equations; RMSNorm with a learned weight, eps ``rms_eps``; no
bias anywhere.  The Olmo family's block (Olmo 2, arXiv 2501.00656): ``h
<- h + RMSNorm(mixer(h))``, then ``h <- h + RMSNorm(MLP(h))``: mixer
and MLP read ``h`` ITSELF, un-normed (every other family here norms the
input).  ``MLP(x) = (SiLU(x W_gate) * (x W_up)) W_down``.  Logits ``=
RMSNorm_f(h) W_head^T``, the head NOT tied.  NO positions anywhere
(``rope_theta: null``): a full layer orders tokens by its causal mask,
a linear layer by its recurrence.

  * LINEAR, Gated DeltaNet (arXiv 2412.06464), ``lin_heads`` heads with
    keys and queries of ``lin_key_dim`` and values of ``lin_value_dim``
    (ops/kda.py has the recurrence and its chunked form for one decay a
    head): ``q~ = SiLU(conv(h W_q))``, ``k~ = SiLU(conv(h W_k))``, ``v =
    SiLU(conv(h W_v))``, three causal depthwise convolutions of kernel
    ``d_conv`` over projections of different widths, run as one over
    their ``conv_width`` channels side by side (a slot keeps the last
    ``d_conv - 1`` inputs, the WINDOW); per head ``q = q~ / |q~| *
    lin_key_dim^-1/2``, ``k = k~ / |k~|`` (eps 1e-6).  ``beta = sigmoid(h
    W_b)``, one a head, doubled where ``neg_eigval``; ``g = -exp(A_log)
    softplus(h W_a + dt_bias)``, ONE a head a token.  State ``S`` (dk,
    dv) float32 a head: ``S' = exp(g_t) S``; ``S_t = S' + beta_t k_t
    (v_t - S'^T k_t)^T``; ``o_t = S_t^T q_t``.  ``out = concat_h(
    RMSNorm_h(o) * SiLU((h W_g)_h)) W_o``, the norm over each head's
    values with one learned weight of ``lin_value_dim``.
  * FULL, multi-head attention: ``q = RMSNorm(h W_q)``, ``k = RMSNorm(h
    W_k)``, each over the WHOLE projection before the heads are cut
    (the family's QK-norm), ``v = h W_v``; ``n_head`` heads of
    ``head_dim`` over ``n_kv_head`` K/V heads (as many, at the
    published sizes), no rotary; ``score = q.k / sqrt(head_dim)`` over
    ``j <= t``, float32 softmax; ``out = concat_h(o_h) W_o``.  K (after
    its norm) and V of a token folded into one row of ``kv_width``
    lanes, as banded_attention.py reads them.

A pad is an identity step of the recurrence (``beta = 0``, ``g = 0``)
and leaves the window alone, as solar_open2.py's pads do
(`deltanet_mix`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu._private import scopes
# the attention over folded K/V, the embedding lookup, the untied head
# and the dense SwiGLU read `dtype`, `rms_eps` and `n_kv_head` off
# whichever config they are handed.  The seeded draw's constants are
# the ones Solar-Open2's linear layers are drawn by, which this
# family's follow
from ray_tpu.models.banded_attention import attend_masked
from ray_tpu.models.layers import (DECAY_SPAN, EMBED_STD, SILU_IN, embed,
                                   lm_logits, nll_from_logits,
                                   plain_rmsnorm, swiglu, unit)
from ray_tpu.models.mamba import conv_inputs
from ray_tpu.ops.kda import kda_decode, kda_prefill
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)

FULL, LINEAR = "full_attention", "linear_attention"
#: the deviation of ``h W_a`` a seeded linear layer is drawn to: in
#: softplus's exponential reach a token's rate ``-g`` is its
#: ``dt_bias``'s times ``exp`` of it, a third of itself up or down
#: (what Solar-Open2's low-rank pair moves its own by)
RATE_SWING = 0.3


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100_352
    max_seq: int = 65_536
    n_layer: int = 32
    #: each layer's kind as the source lists them; entries at or past
    #: ``n_layer`` name nothing
    layer_pattern: Tuple[str, ...] = ((LINEAR,) * 3 + (FULL,)) * 8
    d_model: int = 3840
    n_head: int = 30
    n_kv_head: int = 30
    head_dim: int = 128
    d_ff: int = 11_008
    lin_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    d_conv: int = 4
    #: ``beta`` in (0, 2): the state's transition may reflect
    neg_eigval: bool = True
    rms_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_pad_to: int = 128
    #: queries and keys a tile of the prefill's banded attention
    attn_block: int = 512
    #: tokens a step of the prefill's chunked delta rule (ops/kda.py)
    rule_chunk: int = 64
    #: taken and not read: the harness's rehearsal lays it over every
    #: family's overrides, and this family has one attention path
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if self.n_head % self.n_kv_head:
            raise ValueError(f"n_head {self.n_head} must be a multiple of "
                             f"n_kv_head={self.n_kv_head}")
        if self.d_conv < 2:
            raise ValueError("d_conv must be at least 2")
        if len(self.layer_pattern) < self.n_layer or set(
                self.layer_pattern) - {FULL, LINEAR}:
            raise ValueError(
                f"layer_pattern must name {self.n_layer} layers {FULL!r} "
                f"or {LINEAR!r}, got {self.layer_pattern}")

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return self.layer_pattern[:self.n_layer]

    def layers_of(self, kind: str) -> Tuple[int, ...]:
        """The indices of the layers whose mixer is `kind`."""
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == kind)

    @property
    def kv_width(self) -> int:
        """One token's K (or V) of one full layer, folded into one row."""
        return self.n_kv_head * self.head_dim

    @property
    def key_width(self) -> int:
        """The channels of a linear layer's q (and of its k)."""
        return self.lin_heads * self.lin_key_dim

    @property
    def value_width(self) -> int:
        """The channels of a linear layer's v (and of its gate)."""
        return self.lin_heads * self.lin_value_dim

    @property
    def conv_width(self) -> int:
        """The channels of a linear layer's three convolutions, q, k and
        v side by side: what a slot's window holds a row."""
        return 2 * self.key_width + self.value_width

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p


_PRESETS: Dict[str, Dict[str, Any]] = {
    # two periods; 3 full heads of 16 over as many K/V heads, 3 linear
    # heads (no multiple of 8) with keys of 8 by values of 16; a chunk
    # of 16, so that a prompt of 40 tokens is more than two
    "nano": dict(vocab_size=512, max_seq=128, n_layer=8, d_model=48,
                 n_head=3, n_kv_head=3, head_dim=16, d_ff=96, lin_heads=3,
                 lin_key_dim=8, lin_value_dim=16, attn_block=16,
                 rule_chunk=16),
    # the published config.json, whole
    "olmo-hybrid-7b": {},
}


def olmo_hybrid_config(name: str = "olmo-hybrid-7b",
                       **overrides) -> OlmoHybridConfig:
    """`overrides` may give ``layer_pattern`` as any sequence."""
    kw = dict(_PRESETS[name], **overrides)
    if "layer_pattern" in kw:
        kw["layer_pattern"] = tuple(str(t) for t in kw["layer_pattern"])
    return OlmoHybridConfig(**kw)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def full_params(cfg: OlmoHybridConfig) -> int:
    """q and o; k and v; the two norms over the whole projections."""
    d, w = cfg.d_model, cfg.n_head * cfg.head_dim
    return 2 * d * w + 2 * d * cfg.kv_width + w + cfg.kv_width


def linear_params(cfg: OlmoHybridConfig) -> int:
    """q, k, v, the gate and o; ``W_a`` and ``W_b``; the three
    convolutions; ``A_log``, ``dt_bias`` and the head norm."""
    d, H = cfg.d_model, cfg.lin_heads
    return (d * (cfg.conv_width + cfg.value_width) + cfg.value_width * d
            + 2 * d * H + cfg.d_conv * cfg.conv_width + 2 * H
            + cfg.lin_value_dim)


def olmo_hybrid_param_count(cfg: OlmoHybridConfig) -> int:
    """Embedding and head (untied), the final norm, and per layer its
    mixer, its SwiGLU and two output norms."""
    d = cfg.d_model
    mixers = {FULL: full_params(cfg), LINEAR: linear_params(cfg)}
    return (2 * cfg.vocab_size * d + d
            + sum(mixers[t] for t in cfg.layer_types)
            + cfg.n_layer * (3 * d * cfg.d_ff + 2 * d))


_MIXER_AXES = {
    FULL: ("attn", {"wq": ("embed", "heads", "head_dim"),
                    "wk": ("embed", None), "wv": ("embed", None),
                    "q_norm": (None,), "k_norm": (None,),
                    "wo": ("heads", "head_dim", "embed")}),
    LINEAR: ("lin", {"wq": ("embed", "heads", "head_dim"),
                     "wk": ("embed", "heads", "head_dim"),
                     "wv": ("embed", "heads", "head_dim"),
                     "conv_q": (None, "heads", "head_dim"),
                     "conv_k": (None, "heads", "head_dim"),
                     "conv_v": (None, "heads", "head_dim"),
                     "wa": ("embed", "heads"), "wb": ("embed", "heads"),
                     "dt_bias": ("heads",), "A_log": ("heads",),
                     "wg": ("embed", "heads", "head_dim"),
                     "o_norm": ("head_dim",),
                     "wo": ("heads", "head_dim", "embed")}),
}


def olmo_hybrid_logical_axes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """Pytree (matching olmo_hybrid_init's) of logical-axis tuples."""
    def layer(kind):
        name, axes = _MIXER_AXES[kind]
        return {"ln1": {"scale": ("embed",)}, "ln2": {"scale": ("embed",)},
                name: dict(axes),
                "mlp": {"w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"),
                        "w_down": ("mlp", "embed")}}

    return {"wte": ("vocab", "embed"), "head": ("vocab", "embed"),
            "ln_f": {"scale": ("embed",)},
            "layers": [layer(t) for t in cfg.layer_types]}


def stream_rms(sublayers: int) -> float:
    """What a seeded model's residual stream measures before its
    ``sublayers``-th sublayer: the embedding's `EMBED_STD` and one unit
    a sublayer before it, since each adds its output NORMED with a
    weight of 1."""
    return math.sqrt(EMBED_STD ** 2 + sublayers)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _normal(key, shape, std, dtype):
    """One program a shape: `std`, which differs by the layer, is data."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def olmo_hybrid_init(key, cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """Seeded weights, drawn as solar_open2_init draws its own (its
    constants), for a block that reads the stream un-normed.  A weight
    that reads the stream is N(0, 0.02 / `stream_rms`) of its sublayer,
    so that every sublayer is given what a normed input would give it;
    the others N(0, 0.02) (an output norm takes their scale away);
    norms 1; the embedding N(0, `EMBED_STD`).  The convolutions' taps
    such that a SiLU's input has the deviation `SILU_IN`; ``W_a`` such
    that ``h W_a`` has the deviation `RATE_SWING`, ``A_log`` 0 and
    ``dt_bias`` the inverse softplus of a rate ``-ln(decay)`` drawn
    log-uniform over `DECAY_SPAN` a head: a state that forgets in ten
    tokens tests nothing at 6k.  Every tensor is drawn by a program of
    its own (one a shape), so a float32 draw is never whole beside the
    weights."""
    d, hd, ff = cfg.d_model, cfg.head_dim, cfg.d_ff
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    pd = cfg.param_dtype
    std = 0.02
    keys = iter(jax.random.split(key, 3 + 16 * cfg.n_layer))

    def normal(shape, s=std):
        return _normal(next(keys), shape, s, pd)

    def dt_bias():
        lo, hi = (-math.log(x) for x in DECAY_SPAN)    # rates, hi < lo
        u = jax.random.uniform(next(keys), (H,), jnp.float32)
        rate = jnp.exp(math.log(hi) + u * (math.log(lo) - math.log(hi)))
        return jnp.log(jnp.expm1(rate)).astype(pd)     # softplus^-1

    def mixer(kind, read):
        if kind == FULL:
            return "attn", {"wq": normal((d, cfg.n_head, hd), read),
                            "wk": normal((d, cfg.kv_width), read),
                            "wv": normal((d, cfg.kv_width), read),
                            "q_norm": jnp.ones((cfg.n_head * hd,), pd),
                            "k_norm": jnp.ones((cfg.kv_width,), pd),
                            "wo": normal((cfg.n_head, hd, d))}
        tap = SILU_IN / (std * math.sqrt(d * cfg.d_conv))
        return "lin", {"wq": normal((d, H, dk), read),
                       "wk": normal((d, H, dk), read),
                       "wv": normal((d, H, dv), read),
                       "conv_q": normal((cfg.d_conv, H, dk), tap),
                       "conv_k": normal((cfg.d_conv, H, dk), tap),
                       "conv_v": normal((cfg.d_conv, H, dv), tap),
                       "wa": normal((d, H), read * RATE_SWING / (
                           std * math.sqrt(d))),
                       "wb": normal((d, H), read),
                       "dt_bias": dt_bias(),
                       "A_log": jnp.zeros((H,), pd),
                       "wg": normal((d, H, dv), read),
                       "o_norm": jnp.ones((dv,), pd),
                       "wo": normal((H, dv, d))}

    def layer(i, kind):
        name, weights = mixer(kind, std / stream_rms(2 * i))
        read = std / stream_rms(2 * i + 1)
        return {"ln1": {"scale": jnp.ones((d,), pd)},
                "ln2": {"scale": jnp.ones((d,), pd)}, name: weights,
                "mlp": {"w_gate": normal((d, ff), read),
                        "w_up": normal((d, ff), read),
                        "w_down": normal((ff, d))}}

    return {"wte": normal((cfg.padded_vocab, d), EMBED_STD),
            "head": normal((cfg.padded_vocab, d)),
            "ln_f": {"scale": jnp.ones((d,), pd)},
            "layers": [layer(i, t) for i, t in enumerate(cfg.layer_types)]}


# ---------------------------------------------------------------------------
# the two mixers
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.ATTN_FULL)
def full_project(h, p, cfg: OlmoHybridConfig):
    """h (..., d), the stream itself -> q (..., H, hd), k and v (...,
    kv_width) folded; q and k normed over their whole projections.  No
    rotary."""
    dt = cfg.dtype
    h = h.astype(dt)
    q = plain_rmsnorm(h @ p["wq"].astype(dt).reshape(cfg.d_model, -1),
                 p["q_norm"], cfg.rms_eps)
    k = plain_rmsnorm(h @ p["wk"].astype(dt), p["k_norm"], cfg.rms_eps)
    return (q.reshape(*h.shape[:-1], cfg.n_head, cfg.head_dim), k,
            h @ p["wv"].astype(dt))


def zero_recurrent(cfg: OlmoHybridConfig, batch: int, layers: bool = True):
    """(window, state) of zeros: a sequence that has seen nothing.  The
    window (d_conv - 1, batch, conv_width), compute dtype; the state
    (batch, heads, key dim, value dim), float32.  With `layers`, stacked
    over the linear layers on a leading axis."""
    lead = (len(cfg.layers_of(LINEAR)),) if layers else ()
    return (jnp.zeros(lead + (cfg.d_conv - 1, batch, cfg.conv_width),
                      cfg.dtype),
            jnp.zeros(lead + (batch, cfg.lin_heads, cfg.lin_key_dim,
                              cfg.lin_value_dim), jnp.float32))


@jax.named_scope(scopes.ATTN_LINEAR)
def deltanet_mix(p, h, cfg: OlmoHybridConfig, window, state, real=None,
                 capture=None, layer=None):
    """The Gated DeltaNet mixer on the stream h (B, T, d), before its
    output norm; solar_open2.kda_mix's contract, argument for argument:

    window (d_conv-1, B, conv_width): the convolutions' last inputs,
    compute dtype; state (B, H, dk, dv) float32 or, with `layer` (one
    column only), the linear layers' stack (n_linear, B, H, dk, dv) of
    which this layer's is entry `layer`: a decode wave hands the whole
    stack over and it is updated where it lies.  real (B, T) bool marks
    the columns that hold a token (pads first), and a pad moves neither
    window nor state.  capture: a traced column index (rows all alike)
    after which window and state are also handed back, for a snapshot.
    One column goes through `kda_decode` (on the chip the step kernel
    for one decay a head, ``delta_decode``, on the stack where it lies;
    the `jnp` step elsewhere), more through `kda_prefill` (on the chip
    the kernel for one decay a head, ``delta_chunk``; the `jnp` matmul
    form elsewhere).

    Returns (out (B, T, d), (window, state as it came: a layer's or the
    stack), (window, state) after `capture` or None)."""
    B, T, _ = h.shape
    H, dk, dv, K = (cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim,
                    cfg.d_conv)
    dt, f32 = cfg.dtype, jnp.float32
    h = h.astype(dt)

    def heads(name):
        return jnp.einsum("btd,dh->bth", h, p[name].astype(dt),
                          preferred_element_type=f32)

    x = jnp.concatenate([
        h @ p[name].astype(dt).reshape(cfg.d_model, -1)
        for name in ("wq", "wk", "wv")], axis=-1)
    if real is not None:
        x = jnp.where(real[..., None], x, jnp.zeros((), x.dtype))
    ext = conv_inputs(x, window, real)
    w = jnp.concatenate([p[name].astype(f32).reshape(K, -1)
                         for name in ("conv_q", "conv_k", "conv_v")], axis=-1)
    qkv = jax.nn.silu(sum(ext[:, i:i + T].astype(f32) * w[i]
                          for i in range(K)))
    q = unit(qkv[..., :cfg.key_width].reshape(B, T, H, dk)) * dk ** -0.5
    k = unit(qkv[..., cfg.key_width:2 * cfg.key_width].reshape(
        B, T, H, dk))
    v = qkv[..., 2 * cfg.key_width:].reshape(B, T, H, dv)
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        heads("wa") + p["dt_bias"].astype(f32))
    beta = jax.nn.sigmoid(heads("wb"))
    if cfg.neg_eigval:
        beta = 2.0 * beta
    if real is not None:
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    g = g[..., None]                            # one decay a head
    snap_state = None
    if T == 1:
        # a layer's state alone is a stack of one
        stack, j = (state[None], 0) if layer is None else (state, layer)
        o, stack = kda_decode(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], stack, j)
        o, new_state = o[:, None], stack[0] if layer is None else stack
    else:
        o, new_state, snap_state = kda_prefill(
            q, k, v, g, beta, state, chunk=cfg.rule_chunk, dtype=dt,
            capture=capture)
    gate = (h @ p["wg"].astype(dt).reshape(cfg.d_model, -1)).astype(f32)
    o = plain_rmsnorm(o, p["o_norm"], cfg.rms_eps) \
        * jax.nn.silu(gate).reshape(B, T, H, dv)
    out = o.astype(dt).reshape(B, T, -1) @ p["wo"].astype(dt).reshape(
        -1, cfg.d_model)
    snap = None
    if capture is not None:
        snap = (jax.lax.dynamic_slice_in_dim(
            ext, capture + 1, K - 1, axis=1).swapaxes(0, 1).astype(
                window.dtype), snap_state)
    return (out, (ext[:, T:].swapaxes(0, 1).astype(window.dtype),
                  new_state), snap)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def _close(x, y, scale, cfg: OlmoHybridConfig, scope: str):
    """``x + RMSNorm(y)``: a sublayer's output joins the stream normed,
    under the scope of the sublayer it closes."""
    with jax.named_scope(scope):
        return x + plain_rmsnorm(y, scale, cfg.rms_eps).astype(x.dtype)


def mlp_half(x, p, cfg: OlmoHybridConfig):
    """``x + RMSNorm(MLP(x))``: the second half of every layer."""
    return _close(x, swiglu(x, p["mlp"], cfg), p["ln2"]["scale"], cfg,
                  scopes.MLP)


def full_block(x, p, cfg: OlmoHybridConfig, attend: Callable):
    """One full layer on x (..., d).  ``attend(q, k, v) -> o (..., H,
    hd)`` is the caller's: it owns the cache (and sees this layer's new
    rows, folded)."""
    q, k, v = full_project(x, p["attn"], cfg)
    o = attend(q, k, v)
    with jax.named_scope(scopes.ATTN_FULL):
        dt = cfg.dtype
        out = o.astype(dt).reshape(*o.shape[:-2], -1) \
            @ p["attn"]["wo"].astype(dt).reshape(-1, cfg.d_model)
    return mlp_half(_close(x, out, p["ln1"]["scale"], cfg,
                           scopes.ATTN_FULL), p, cfg)


def linear_block(x, p, cfg: OlmoHybridConfig, window, state, real=None,
                 capture=None, layer=None):
    """One linear layer on x (B, T, d) from (`window`, `state`),
    `deltanet_mix`'s arguments.  Returns (x, (window, state) after the
    last column, the same after `capture` or None)."""
    out, after, snap = deltanet_mix(p["lin"], x, cfg, window, state, real,
                                    capture, layer)
    x = _close(x, out, p["ln1"]["scale"], cfg, scopes.ATTN_LINEAR)
    return mlp_half(x, p, cfg), after, snap


def walk_layers(cfg: OlmoHybridConfig, params, x, layer: Callable):
    """`x` through the layers, unrolled.  ``layer(x, p, kind, j) -> x``
    is a layer with weights `p`, the `j`-th of its kind."""
    seen = {FULL: 0, LINEAR: 0}
    for p, kind in zip(params["layers"], cfg.layer_types):
        x = layer(x, p, kind, seen[kind])
        seen[kind] += 1
    return x


def olmo_hybrid_hidden(params, tokens, cfg: OlmoHybridConfig,
                       rules=DEFAULT_RULES):
    """tokens (B, T) -> final hidden (B, T, d): the full-sequence
    forward, no cache, every sequence from a zero state."""
    B, T = tokens.shape
    mask = jnp.tril(jnp.ones((T, T), bool))[None]
    window, state = zero_recurrent(cfg, B, layers=False)
    x = with_logical_constraint(embed(params, tokens, cfg),
                                ("batch", "seq", "embed"), rules)

    def layer(x, p, kind, j):
        if kind == FULL:
            def attend(q, k, v):
                with jax.named_scope(scopes.ATTN_FULL):
                    return attend_masked(q, k, v, mask, cfg)

            x = full_block(x, p, cfg, attend)
        else:
            x = linear_block(x, p, cfg, window, state)[0]
        return with_logical_constraint(x, ("batch", "seq", "embed"), rules)

    return walk_layers(cfg, params, x, layer)


def olmo_hybrid_forward(params, tokens, cfg: OlmoHybridConfig,
                        rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    hidden = olmo_hybrid_hidden(params, tokens, cfg, rules)
    return with_logical_constraint(lm_logits(hidden, params, cfg),
                                   ("batch", "seq", "vocab"), rules)


def olmo_hybrid_loss(params, batch, cfg: OlmoHybridConfig,
                     rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}, optionally {"mask"}.  A forward's number:
    the chunked delta rule has no backward of its own."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    nll = nll_from_logits(olmo_hybrid_forward(params, inputs, cfg, rules),
                          targets, cfg.vocab_size, cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


__all__ = ["OlmoHybridConfig", "olmo_hybrid_config", "olmo_hybrid_init",
           "olmo_hybrid_forward", "olmo_hybrid_loss",
           "olmo_hybrid_logical_axes", "olmo_hybrid_param_count", "FULL",
           "LINEAR"]
