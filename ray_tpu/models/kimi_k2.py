"""Kimi-K2 family decoder (``model_type: kimi_k2``; DeepSeek-V3's block
at other numbers): latent attention (MLA) and a sparse expert layer.

Same template as gpt2.py / llama.py / jamba.py (pure init/apply over
pytrees, logical axes, bf16 compute over float32 or bf16 weights).  The
first ``n_dense`` layers end in a dense SwiGLU MLP, the others in the
expert layer of models/experts.py; parameters are stacked BY KIND
(``params["dense"]``, ``params["moe"]``) and each kind is scanned
(`walk_layers`), so a compiled program holds each kind of layer once.

The layer equations, ``u = RMSNorm(h)``, no bias anywhere:

  * MLA.  ``c_q = RMSNorm(W_qa u)``; ``[q_nope | q_pe] = W_qb c_q`` per
    head; ``[c_kv | k_pe] = W_kva u``; ``c_kv <- RMSNorm(c_kv)``;
    ``q_pe, k_pe <- RoPE`` (``k_pe`` is ONE vector for all heads);
    ``k_nope = W_uk c_kv``, ``v = W_uv c_kv`` per head;
    ``score = (q_nope.k_nope + q_pe.k_pe) * s``, causal softmax,
    ``out = W_o concat_h(softmax . v)``.  What a token leaves behind per
    layer is ``c_kv`` (after its norm) and ``k_pe`` (after RoPE):
    ``kv_lora_rank + qk_rope_dim`` values, no K or V per head.
  * the same numbers, ABSORBED (a decode step): ``q~ = q_nope W_uk,h``,
    ``score = (q~.c_kv + q_pe.k_pe) * s``, ``o_h = (softmax . c_kv)
    W_uv,h``: attention over the latent itself, `attend_absorbed`.  A
    prefill up-projects the latents once and attends EXPANDED
    (`attend_expanded`, and blockwise in kimi_k2_decode.py).
  * RoPE with YaRN (`yarn_inv_freq`): ``inv_freq = f/factor * (1 - m) +
    f * m`` with ``f_j = theta^(-2j/rope_dim)`` and ``m`` a ramp over
    DeepSeek-V3's correction range; ``s = qk_head_dim^-1/2 *
    mscale(factor, mscale_all_dim)^2`` (`softmax_scale`).  Pairs
    ``(2i, 2i+1)`` rotate together, as `models/llama.py apply_rope`
    pairs them (the source stores its rotary columns so and permutes
    them before a half-split rotation: a fixed permutation of ``W_qb``
    and ``W_kva`` columns, the same model under seeded weights).
  * FFN: layer ``i < n_dense``: ``W_down(silu(W_gate m) * W_up m)`` of
    width ``d_ff``; the others: `experts.moe_layer` on ``m =
    RMSNorm(h)`` in float32 (the router decides in float32).
  * logits ``= RMSNorm(h) W_head^T``: the head is NOT tied.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models import experts as ex
from ray_tpu.models.layers import (embed, lm_logits, nll_from_logits,
                                   plain_rmsnorm, rmsnorm, rotate, swiglu,
                                   yarn_inv_freq)
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163_840
    max_seq: int = 4096
    n_layer: int = 61
    #: layers 0..n_dense-1 end in the dense MLP (first_k_dense_replace)
    n_dense: int = 1
    n_head: int = 64
    d_model: int = 7168
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 18_432
    d_expert: int = 2048
    n_routed: int = 384
    #: which of the n_routed experts this chip holds (experts.py); None
    #: holds them all
    held: Optional[Tuple[int, ...]] = None
    top_k: int = 8
    n_shared: int = 1
    scoring: str = "sigmoid"
    norm_topk: bool = True
    route_scale: float = 2.827
    rope_theta: float = 50_000.0
    #: YaRN; factor 1 is plain RoPE
    rope_factor: float = 64.0
    rope_orig_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_pad_to: int = 128
    #: queries and keys a paged prefill attends at once (its score
    #: matrix over a whole 8k prompt would not fit the chip)
    attn_block: int = 512
    #: taken, and read by nothing: the harness lays it over every
    #: family's overrides, and this family's attention has no kernel
    use_flash: Optional[bool] = None

    def __post_init__(self):
        if not 0 <= self.n_dense <= self.n_layer:
            raise ValueError(f"n_dense {self.n_dense} outside "
                             f"0..n_layer={self.n_layer}")
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim must be even")
        self.experts  # its own checks

    @property
    def n_moe(self) -> int:
        return self.n_layer - self.n_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_dim(self) -> int:
        """What one token leaves in the cache per layer."""
        return self.kv_lora_rank + self.qk_rope_dim

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
            dtype=self.dtype, param_dtype=self.param_dtype)


_PRESETS: Dict[str, Dict[str, Any]] = {
    # three layers (one dense), 16 experts of which a token takes 4
    "nano": dict(vocab_size=512, max_seq=128, n_layer=3, n_dense=1,
                 n_head=4, d_model=64, q_lora_rank=32, kv_lora_rank=32,
                 qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, d_ff=128,
                 d_expert=32, n_routed=16, top_k=4, attn_block=32,
                 rope_orig_max=32, rope_factor=4.0),
    # the published config.json, whole
    "kimi-k2-code": {},
}


def kimi_k2_config(name: str = "kimi-k2-code",
                   **overrides) -> KimiK2Config:
    """`overrides` may give ``held`` as any sequence of expert ids."""
    kw = dict(_PRESETS[name], **overrides)
    if kw.get("held") is not None:
        kw["held"] = tuple(int(e) for e in kw["held"])
    return KimiK2Config(**kw)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def _mla_params(cfg: KimiK2Config) -> int:
    d, H = cfg.d_model, cfg.n_head
    return (d * cfg.q_lora_rank + cfg.q_lora_rank
            + cfg.q_lora_rank * H * cfg.qk_head_dim
            + d * cfg.latent_dim + cfg.kv_lora_rank
            + cfg.kv_lora_rank * H * (cfg.qk_nope_dim + cfg.v_head_dim)
            + H * cfg.v_head_dim * d)


def kimi_k2_param_count(cfg: KimiK2Config) -> int:
    """Embedding and head (untied), the final norm, and per layer its
    MLA, two norms and its FFN; an expert layer counts the experts it
    HOLDS."""
    d = cfg.d_model
    layer = _mla_params(cfg) + 2 * d
    return (2 * cfg.vocab_size * d + d
            + cfg.n_dense * (layer + 3 * d * cfg.d_ff)
            + cfg.n_moe * (layer + ex.experts_param_count(cfg.experts)))


def _attn_axes() -> Dict[str, Any]:
    return {"wq_a": (None, "embed", None), "q_norm": (None, None),
            "wq_b": (None, None, "heads", "head_dim"),
            "wkv_a": (None, "embed", None), "kv_norm": (None, None),
            "wk_b": (None, None, "heads", "head_dim"),
            "wv_b": (None, None, "heads", "head_dim"),
            "wo": (None, "heads", "head_dim", "embed")}


def kimi_k2_logical_axes(cfg: KimiK2Config) -> Dict[str, Any]:
    """Pytree (matching kimi_k2_init's) of logical-axis tuples; the
    leading None on a layer's leaves is its kind's stacked axis."""
    norms = {"ln1": {"scale": (None, "embed")},
             "ln2": {"scale": (None, "embed")}}
    return {
        "wte": ("vocab", "embed"), "head": ("vocab", "embed"),
        "ln_f": {"scale": ("embed",)},
        "dense": dict(norms, attn=_attn_axes(), mlp={
            "w_gate": (None, "embed", "mlp"),
            "w_up": (None, "embed", "mlp"),
            "w_down": (None, "mlp", "embed")}),
        "moe": dict(norms, attn=_attn_axes(),
                    moe=ex.experts_logical_axes(cfg.experts, (None,))),
    }


def kimi_k2_init(key, cfg: KimiK2Config) -> Dict[str, Any]:
    """Seeded weights: projections N(0, 0.02), those into the residual
    stream scaled by 1/sqrt(2 n_layer), norms 1; the router float32 with
    a small non-zero selection bias (experts.experts_init).  A stack of
    layers is drawn a layer at a time, so its float32 draw is never
    whole beside the weights (the experts of five layers are 3.5 GB in
    float32)."""
    d, H = cfg.d_model, cfg.n_head
    pd = cfg.param_dtype
    std = 0.02
    res_std = std / math.sqrt(2 * cfg.n_layer)
    keys = iter(jax.random.split(key, 32))

    def normal(shape, s=std):
        k = next(keys)
        draw = lambda kk, sh: (jax.random.normal(  # noqa: E731
            kk, sh, jnp.float32) * s).astype(pd)
        if len(shape) < 3 or not shape[0]:
            return draw(k, shape) if all(shape) else jnp.zeros(shape, pd)
        return jax.jit(lambda kk: lax.map(
            lambda one: draw(one, shape[1:]),
            jax.random.split(kk, shape[0])))(k)

    def attn(L):
        return {"wq_a": normal((L, d, cfg.q_lora_rank)),
                "q_norm": jnp.ones((L, cfg.q_lora_rank), pd),
                "wq_b": normal((L, cfg.q_lora_rank, H, cfg.qk_head_dim)),
                "wkv_a": normal((L, d, cfg.latent_dim)),
                "kv_norm": jnp.ones((L, cfg.kv_lora_rank), pd),
                "wk_b": normal((L, cfg.kv_lora_rank, H, cfg.qk_nope_dim)),
                "wv_b": normal((L, cfg.kv_lora_rank, H, cfg.v_head_dim)),
                "wo": normal((L, H, cfg.v_head_dim, d), res_std)}

    def norms(L):
        return {"ln1": {"scale": jnp.ones((L, d), pd)},
                "ln2": {"scale": jnp.ones((L, d), pd)}}

    Ld, Lm = cfg.n_dense, cfg.n_moe
    moe = jax.jit(lambda k: lax.map(
        lambda one: ex.experts_init(one, cfg.experts, std=std,
                                    out_std=res_std),
        jax.random.split(k, Lm)))(next(keys)) if Lm else \
        jax.tree.map(lambda a: jnp.zeros((0, *a.shape), a.dtype),
                     jax.eval_shape(lambda: ex.experts_init(
                         jax.random.PRNGKey(0), cfg.experts)))
    return {
        "wte": normal((cfg.padded_vocab, d)),
        "head": normal((cfg.padded_vocab, d)),
        "ln_f": {"scale": jnp.ones((d,), pd)},
        "dense": dict(norms(Ld), attn=attn(Ld), mlp={
            "w_gate": normal((Ld, d, cfg.d_ff)),
            "w_up": normal((Ld, d, cfg.d_ff)),
            "w_down": normal((Ld, cfg.d_ff, d), res_std)}),
        "moe": dict(norms(Lm), attn=attn(Lm), moe=moe),
    }


# ---------------------------------------------------------------------------
# rotary positions with YaRN
# ---------------------------------------------------------------------------

def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: KimiK2Config) -> float:
    """``qk_head_dim^-1/2 * mscale(factor, mscale_all_dim)^2``: 0.14468
    for the published numbers."""
    return cfg.qk_head_dim ** -0.5 \
        * _mscale(cfg.rope_factor, cfg.mscale_all_dim) ** 2


def rope_tables(positions, cfg: KimiK2Config):
    """cos, sin (..., qk_rope_dim / 2) float32 at int `positions`
    (...); YaRN's cos/sin factor (1 where mscale == mscale_all_dim) is
    in them."""
    ang = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    gain = _mscale(cfg.rope_factor, cfg.mscale) \
        / _mscale(cfg.rope_factor, cfg.mscale_all_dim)
    return jnp.cos(ang) * gain, jnp.sin(ang) * gain


# ---------------------------------------------------------------------------
# MLA
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.MLA)
def mla_project(u, p, cfg: KimiK2Config, cos, sin, latent: bool = False):
    """u (B, T, d) normed input; cos, sin (B or 1, T, rope/2) ->
    q (B, T, H, nope + rope) with its rotary part rotated, and what the
    cache keeps: ckv (B, T, kv_lora_rank) normed, kpe (B, T, rope)
    rotated.  With `latent` the query latent ``c_q`` (B, T,
    q_lora_rank) is handed out as a fourth (models/glm_dsa.py: an
    indexer projects its own queries from it)."""
    dt = cfg.dtype
    B, T, _ = u.shape
    H, n = cfg.n_head, cfg.qk_nope_dim
    u = u.astype(dt)
    cq = plain_rmsnorm(u @ p["wq_a"].astype(dt), p["q_norm"], cfg.rms_eps)
    q = (cq @ p["wq_b"].astype(dt).reshape(cfg.q_lora_rank, -1)
         ).reshape(B, T, H, cfg.qk_head_dim)
    q = jnp.concatenate(
        [q[..., :n], rotate(q[..., n:], cos[:, :, None], sin[:, :, None])],
        axis=-1)
    kv = u @ p["wkv_a"].astype(dt)
    ckv = plain_rmsnorm(kv[..., :cfg.kv_lora_rank], p["kv_norm"], cfg.rms_eps)
    kpe = rotate(kv[..., cfg.kv_lora_rank:], cos, sin)
    return (q, ckv, kpe, cq) if latent else (q, ckv, kpe)


@jax.named_scope(scopes.MLA)
def expand_latents(ckv, p, cfg: KimiK2Config):
    """Latents (..., S, kv_lora_rank) up to the per-head keys' position
    free part (..., S, H, nope) and V (..., S, H, v)."""
    dt = cfg.dtype
    ckv = ckv.astype(dt)
    return (jnp.einsum("...sc,chn->...shn", ckv, p["wk_b"].astype(dt)),
            jnp.einsum("...sc,chv->...shv", ckv, p["wv_b"].astype(dt)))


@jax.named_scope(scopes.MLA)
def expand_keys(ckv, kpe, p, cfg: KimiK2Config):
    """Latents (..., S, kv_lora_rank) and rotary keys (..., S, rope) up
    to per-head K (..., S, H, nope + rope) and V (..., S, H, v)."""
    kn, v = expand_latents(ckv, p, cfg)
    kr = jnp.broadcast_to(kpe.astype(cfg.dtype)[..., None, :],
                          (*kn.shape[:-1], cfg.qk_rope_dim))
    return jnp.concatenate([kn, kr], axis=-1), v


@jax.named_scope(scopes.MLA)
def attend_expanded(q, ckv, kpe, p, mask, cfg: KimiK2Config):
    """q (B, T, H, qk) over latents ckv (B, S, c) and kpe (B, S, r)
    under mask (B, T, S), keys and values up-projected: (B, T, H, v)."""
    k, v = expand_keys(ckv, kpe, p, cfg)
    s = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32)
    s = jnp.where(mask[:, None], s * softmax_scale(cfg), -1e30)
    probs = jax.nn.softmax(s, axis=-1).astype(cfg.dtype)
    return jnp.einsum("bhts,bshv->bthv", probs, v)


@jax.named_scope(scopes.MLA)
def attend_absorbed(q, ckv, kpe, p, mask, cfg: KimiK2Config, fresh=None):
    """The same attention with ``W_uk`` folded into the query and
    ``W_uv`` into the output: q (B, T, H, qk) scores the latents
    themselves, nothing per head is built of the S cached positions.

    `fresh` = (ckv (B, 1, c), kpe (B, 1, r)), a decode step's own new
    row: attended as one more key beside the view's S slots (`mask`
    then leaves the row's own slot out), so the row need not be
    written into a copy of the view first."""
    dt, n = cfg.dtype, cfg.qk_nope_dim
    q_lat = jnp.einsum("bthn,chn->bthc", q[..., :n], p["wk_b"].astype(dt))

    def scores(c, r):
        return (jnp.einsum("bthc,bsc->bhts", q_lat, c.astype(dt))
                + jnp.einsum("bthr,bsr->bhts", q[..., n:], r.astype(dt))
                ).astype(jnp.float32) * softmax_scale(cfg)

    s = jnp.where(mask[:, None], scores(ckv, kpe), -1e30)
    if fresh is not None:
        s = jnp.concatenate([s, scores(*fresh)], axis=-1)
    probs = jax.nn.softmax(s, axis=-1).astype(dt)
    S = ckv.shape[1]
    o_lat = jnp.einsum("bhts,bsc->bthc", probs[..., :S], ckv.astype(dt))
    if fresh is not None:
        o_lat = o_lat + jnp.einsum("bhts,bsc->bthc", probs[..., S:],
                                   fresh[0].astype(dt))
    return jnp.einsum("bthc,chv->bthv", o_lat, p["wv_b"].astype(dt))


@jax.named_scope(scopes.MLA)
def mla_out(o, p, cfg: KimiK2Config):
    """o (B, T, H, v) through ``W_o``: (B, T, d)."""
    B, T = o.shape[:2]
    return o.reshape(B, T, -1).astype(cfg.dtype) \
        @ p["wo"].astype(cfg.dtype).reshape(-1, cfg.d_model)


# ---------------------------------------------------------------------------
# the block and the walk over layers of two kinds
# ---------------------------------------------------------------------------


def block(x, p, cfg: KimiK2Config, positions, attend: Callable,
          valid=None, tiled: bool = True,
          indexer: Optional[Callable] = None):
    """One layer on x (B, T, d) at int `positions` (B or 1, T).
    ``attend(q, ckv, kpe) -> o (B, T, H, v)`` is the caller's: it owns
    the cache (and sees this layer's new latent rows).  `valid` (B, T)
    marks the rows that hold a token (experts.routed_experts).  A layer
    of ``p`` with a ``"moe"`` entry is an expert layer.

    A family whose attention reads only what a learned indexer selects
    (models/glm_dsa.py) gives ``indexer(u, c_q, cos, sin) -> tuple``:
    what it returns from the layer's normed input and query latent is
    handed to `attend` after the three.

    Returns (x, per-layer experts.STATS or None)."""
    cos, sin = rope_tables(positions, cfg)
    u = rmsnorm(x, p["ln1"]["scale"], cfg.rms_eps)
    if indexer is None:
        o = attend(*mla_project(u, p["attn"], cfg, cos, sin))
    else:
        q, ckv, kpe, cq = mla_project(u, p["attn"], cfg, cos, sin,
                                      latent=True)
        o = attend(q, ckv, kpe, *indexer(u, cq, cos, sin))
    x = x + mla_out(o, p["attn"], cfg).astype(x.dtype)
    if "moe" not in p:
        return x + swiglu(rmsnorm(x, p["ln2"]["scale"], cfg.rms_eps),
                          p["mlp"], cfg), None
    B, T, d = x.shape
    # the router reads the norm in float32, not through the stream's
    # bf16: which experts run is decided there
    m = rmsnorm(x.astype(jnp.float32), p["ln2"]["scale"], cfg.rms_eps)
    y, stats = ex.moe_layer(
        p["moe"], m.reshape(B * T, d), cfg.experts,
        None if valid is None else valid.reshape(B * T), tiled)
    return x + y.reshape(B, T, d).astype(x.dtype), stats


def walk_layers(cfg: KimiK2Config, params, x, carry, layer: Callable):
    """`x` through all ``n_layer`` layers: the dense stack scanned, then
    the expert stack.  ``layer(x, carry, p, lidx) -> (x, carry, ys,
    stats)`` is layer `lidx` of the model with weights `p`; `carry` is
    whatever the caller threads through (the cache pools, updated where
    they lie).  Returns (x, carry, ys stacked over all layers, the
    expert layers' stats (n_moe, len(STATS)))."""
    def run(x, carry, stack, first, count, whole=None):
        def body(c, xs):
            p, j = xs
            if whole is not None:
                p = dict(p, moe=dict(p["moe"], experts=whole, layer=j))
            x, carry, ys, stats = layer(*c, p, first + j)
            return (x, carry), (ys, stats)

        with jax.named_scope(scopes.LAYER_SCAN):
            (x, carry), (ys, stats) = lax.scan(
                body, (x, carry),
                (stack, jnp.arange(count, dtype=jnp.int32)))
        return x, carry, ys, stats

    x, carry, ys, _ = run(x, carry, params["dense"], 0, cfg.n_dense)
    # the experts' stack stays out of the scan's sliced inputs: the
    # grouped matmul takes it whole (experts._grouped)
    moe = dict(params["moe"])
    moe["moe"] = {k: v for k, v in moe["moe"].items() if k != "experts"}
    x, carry, ys_m, stats = run(x, carry, moe, cfg.n_dense, cfg.n_moe,
                                params["moe"]["moe"]["experts"])
    ys = jax.tree.map(lambda a, b: jnp.concatenate([a, b], axis=0),
                      ys, ys_m)
    return x, carry, ys, stats


def kimi_k2_hidden(params, tokens, cfg: KimiK2Config, rules=DEFAULT_RULES):
    """tokens (B, T) -> (final hidden (B, T, d), expert stats): the
    full-sequence forward, causal, no cache.  Every sorted assignment
    goes through one grouped matmul (``tiled=False``), so the forward
    differentiates."""
    B, T = tokens.shape
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    mask = jnp.tril(jnp.ones((T, T), bool))[None]
    x = with_logical_constraint(embed(params, tokens, cfg),
                                ("batch", "seq", "embed"), rules)

    def layer(x, carry, p, lidx):
        def attend(q, ckv, kpe):
            return attend_expanded(q, ckv, kpe, p["attn"], mask, cfg)

        x, stats = block(x, p, cfg, positions, attend, tiled=False)
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
        return x, carry, (), stats

    x, _, _, stats = walk_layers(cfg, params, x, (), layer)
    return x, stats


def kimi_k2_forward(params, tokens, cfg: KimiK2Config,
                    rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    hidden, _ = kimi_k2_hidden(params, tokens, cfg, rules)
    return with_logical_constraint(lm_logits(hidden, params, cfg),
                                   ("batch", "seq", "vocab"), rules)


def kimi_k2_loss(params, batch, cfg: KimiK2Config, rules=DEFAULT_RULES,
                 forward: Callable = kimi_k2_forward) -> jnp.ndarray:
    """Next-token cross-entropy; batch = {"tokens": (B, T+1)} or
    {"inputs", "targets"}, optionally {"mask"} (the NLL shared with the
    other families).  No auxiliary balance loss: the source balances by
    its selection bias (``noaux_tc``), which training would update
    outside the gradient and nothing here trains.  `forward` is the
    family's own where another shares this loss (models/glm_dsa.py)."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    nll = nll_from_logits(forward(params, inputs, cfg, rules), targets,
                          cfg.vocab_size, cfg.padded_vocab)
    mask = batch.get("mask")
    if mask is not None:
        m = mask.astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


__all__ = ["KimiK2Config", "kimi_k2_config", "kimi_k2_init",
           "kimi_k2_forward", "kimi_k2_loss", "kimi_k2_logical_axes",
           "kimi_k2_param_count"]
