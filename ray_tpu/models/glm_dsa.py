"""GLM-5 family decoder (``model_type: glm_moe_dsa``): DeepSeek-V3's
block (models/kimi_k2.py: latent attention, a sigmoid ``noaux_tc``
router over routed and shared experts) whose attention reads only the
positions a learned indexer picks (DeepSeek Sparse Attention,
ops/dsa.py).

Everything but the selection IS models/kimi_k2.py's, imported: the
config's fields, `mla_project`, the three attention forms, `mla_out`,
`block`, `walk_layers`, the norm, the embedding and the untied head;
the experts are models/experts.py's.  ``d`` the hidden size; RMSNorm
with a learned weight; no bias but the index key's LayerNorm.  Layer
``i``, ``u = RMSNorm(h)``:

  * MLA as kimi_k2.py states it: ``c_q = RMSNorm(W_qa u)``; ``[q_nope |
    q_pe]_h = W_qb c_q``; ``[c_kv | k_pe] = W_kva u``; ``c_kv <-
    RMSNorm(c_kv)``; RoPE on ``q_pe``, ``k_pe``; ``k_nope_h = W_uk,h
    c_kv``, ``v_h = W_uv,h c_kv``; scale ``qk_head_dim^-1/2``.  The
    published numbers have values WIDER than the keys' position-free
    part (``v_head_dim`` 256, ``qk_nope_head_dim`` 192) and plain RoPE
    (``rope_factor`` 1: no YaRN, ``mscale`` 1), pairs ``(2i, 2i+1)``.
  * the indexer, ``J = index_n_heads`` of ``D = index_head_dim``, the
    first ``qk_rope_dim`` of each ``D`` rotated by ``k_pe``'s tables
    (`index_project`): ``q^I_{t,j} = RoPE((W^I_q c_q)_j)``; ``k^I_s =
    RoPE(LayerNorm(W^I_k u_s))``, ONE key a token for all heads (weight
    and bias, eps 1e-6): the cache's third per-position tensor,
    ``kidx``; ``w_{t,j} = (W^I_w u_t)_j J^-1/2 D^-1/2`` in float32;
    ``I_{t,s} = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)``.
  * query ``t`` attends, by the MLA softmax, the ``min(index_topk, t +
    1)`` positions ``s <= t`` of highest ``I_{t,s}`` and no other (its
    own position competes; ties go to the lower position).  Every
    layer has its own indexer, a dense layer too.
  * FFN and logits as kimi_k2.py's: layers ``i < n_dense`` a SwiGLU of
    ``d_ff``, the others `experts.moe_layer`; ``RMSNorm(h) W_head^T``.

The source's multi-token-prediction module (``num_nextn_predict_layers``)
is no part of this forward pass and has no weights here.

Seeded weights (`glm_dsa_init`): kimi_k2.py's draw, and the indexer's
three matrices N(0, 1 / fan-in), its LayerNorm weight 1 and bias N(0,
0.02).  At the published widths that gives unit ``q^I`` and ``k^I``
components, ``q . k`` of deviation 11.3 and ``w`` of 0.016; over 12,288
positions ``I`` has deviation 0.51 to 0.77 a query, and the 2,048th
highest stands 1.6e-4 (median; 2e-5 to 1.1e-3) above the 2,049th.  bf16
operands move a score by 1.7e-3 (deviation; 9.6e-3 at most), ten times
that spacing: the program's selection and a float32 reference's differ
at 1 to 5 of 2,048 places a query, none further than 4.3e-3 from the
last place by the reference's own score (sixteen queries, float32
against bf16-rounded operands on the CPU; tests/test_glm_dsa.py
compares the sets within such a band before it compares logits).

How much the selection MATTERS is the draw's to say, and kimi_k2's says
little: its N(0, 0.02) attention adds to the stream a hundredth of what
the FFN adds, under a nearly flat softmax.  A draw that makes the
attention weigh as the FFN does (its matrices N(0, 1 / fan-in), queries
doubled) was tried and NOT kept: under seeded weights every attended
row weighs alike and the values are uncorrelated, so the few places at
which a bf16 selection differs from a float32 one (the edge of the
selection, where a trained indexer has put rows the attention hardly
weighs) move an attention output by the square root of their share,
the next layer's index scores move with the stream, and five layers on
a third of the engine's greedy tokens were no longer the float32
reference's (largest logit gap 2.1 to 2.4 against 0.66 to 0.73 under
the draw kept; my chip runs, PR 58).  Where a test needs the selection
loud it makes the same rescaling itself
(tests/benchmark/test_glm_dsa.py `_loud`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models import kimi_k2 as K
from ray_tpu.models.layers import embed, layernorm, lm_logits, rotate
from ray_tpu.ops import dsa
from ray_tpu.parallel.sharding import (DEFAULT_RULES,
                                       with_logical_constraint)


@dataclasses.dataclass(frozen=True)
class GlmDsaConfig(K.KimiK2Config):
    """kimi_k2.KimiK2Config at GLM-5's published numbers, and the
    indexer's three."""
    vocab_size: int = 154_880
    n_layer: int = 78
    n_dense: int = 3
    d_model: int = 6144
    q_lora_rank: int = 2048
    qk_nope_dim: int = 192
    v_head_dim: int = 256
    d_ff: int = 12_288
    n_routed: int = 256
    route_scale: float = 2.5
    rope_theta: float = 1_000_000.0
    rope_factor: float = 1.0
    index_n_heads: int = 32
    index_head_dim: int = 128
    #: positions a query attends at most
    index_topk: int = 2048
    #: the index key's LayerNorm
    index_eps: float = 1e-6

    def __post_init__(self):
        super().__post_init__()
        if self.index_head_dim < self.qk_rope_dim:
            raise ValueError("index_head_dim must hold the rotary part")

    @property
    def index_scale(self) -> float:
        return self.index_n_heads ** -0.5 * self.index_head_dim ** -0.5


_PRESETS: Dict[str, Dict[str, Any]] = {
    # one dense and two expert layers; 8 of 16 experts held; values
    # wider than the keys' position-free part; a selection of 24,
    # larger than a block of 16 and smaller than the tests' prompts
    "nano": dict(vocab_size=512, max_seq=128, n_layer=3, n_dense=1,
                 n_head=4, d_model=64, q_lora_rank=32, kv_lora_rank=32,
                 qk_nope_dim=12, qk_rope_dim=8, v_head_dim=16, d_ff=128,
                 d_expert=32, n_routed=16, held=tuple(range(8)), top_k=4,
                 attn_block=16, index_n_heads=4, index_head_dim=16,
                 index_topk=24),
    # the published config.json, whole
    "glm-5": {},
}


def glm_dsa_config(name: str = "glm-5", **overrides) -> GlmDsaConfig:
    """`overrides` may give ``held`` as any sequence of expert ids."""
    kw = dict(_PRESETS[name], **overrides)
    if kw.get("held") is not None:
        kw["held"] = tuple(int(e) for e in kw["held"])
    return GlmDsaConfig(**kw)


def _indexer_params(cfg: GlmDsaConfig) -> int:
    J, D = cfg.index_n_heads, cfg.index_head_dim
    return cfg.q_lora_rank * J * D + cfg.d_model * D + 2 * D \
        + cfg.d_model * J


def glm_dsa_param_count(cfg: GlmDsaConfig) -> int:
    """kimi_k2's count and an indexer a layer."""
    return K.kimi_k2_param_count(cfg) + cfg.n_layer * _indexer_params(cfg)


def _indexer_axes() -> Dict[str, Any]:
    return {"wq": (None, None, "heads"),
            "wk": (None, "embed", None),
            "k_norm": {"scale": (None, None), "bias": (None, None)},
            "ww": (None, "embed", None)}


def glm_dsa_logical_axes(cfg: GlmDsaConfig) -> Dict[str, Any]:
    axes = K.kimi_k2_logical_axes(cfg)
    for kind in ("dense", "moe"):
        axes[kind] = dict(axes[kind], indexer=_indexer_axes())
    return axes


def glm_dsa_init(key, cfg: GlmDsaConfig) -> Dict[str, Any]:
    """kimi_k2_init's tree with ``"indexer"`` beside ``"attn"`` in both
    stacks: the indexer's three matrices N(0, 1 / fan-in), its
    LayerNorm weight 1 and bias N(0, 0.02) (the module docstring has
    what the draw gives)."""
    base, ki = jax.random.split(key)
    params = K.kimi_k2_init(base, cfg)
    pd, d = cfg.param_dtype, cfg.d_model
    J, D = cfg.index_n_heads, cfg.index_head_dim

    def indexer(k, L):
        ks = jax.random.split(k, 4)

        def normal(kk, shape, fan_in):
            return (jax.random.normal(kk, shape, jnp.float32)
                    * fan_in ** -0.5).astype(pd)

        return {"wq": normal(ks[0], (L, cfg.q_lora_rank, J * D),
                             cfg.q_lora_rank),
                "wk": normal(ks[1], (L, d, D), d),
                "k_norm": {"scale": jnp.ones((L, D), pd),
                           "bias": normal(ks[2], (L, D), 2500)},
                "ww": normal(ks[3], (L, d, J), d)}

    for kind, k, L in zip(("dense", "moe"), jax.random.split(ki),
                          (cfg.n_dense, cfg.n_moe)):
        params[kind]["indexer"] = indexer(k, L)
    return params


@jax.named_scope(scopes.ATTN_INDEX)
def index_project(u, cq, p, cfg: GlmDsaConfig, cos, sin):
    """A layer's indexer from its normed input u (B, T, d) and query
    latent cq (B, T, q_lora_rank), at the rotary tables cos, sin (B or
    1, T, rope/2): queries qi (B, T, J, D), head weights w (B, T, J)
    float32 with ``J^-1/2 D^-1/2`` in them, and the ONE key a token the
    cache keeps, kidx (B, T, D), after its LayerNorm and rotary."""
    dt, r = cfg.dtype, cfg.qk_rope_dim
    B, T, _ = u.shape
    J, D = cfg.index_n_heads, cfg.index_head_dim
    u = u.astype(dt)

    def rotated(x, c, s):
        return jnp.concatenate([rotate(x[..., :r], c, s), x[..., r:]],
                               axis=-1)

    qi = rotated((cq.astype(dt) @ p["wq"].astype(dt)).reshape(B, T, J, D),
                 cos[:, :, None], sin[:, :, None])
    kidx = rotated(layernorm(u @ p["wk"].astype(dt), p["k_norm"]["scale"],
                              p["k_norm"]["bias"], cfg.index_eps), cos, sin)
    w = jnp.einsum("btd,dj->btj", u.astype(jnp.float32),
                   p["ww"].astype(jnp.float32),
                   precision=lax.Precision.HIGHEST) * cfg.index_scale
    return qi, w, kidx


def block(x, p, cfg: GlmDsaConfig, positions, attend, valid=None,
          tiled: bool = True):
    """kimi_k2.block with this layer's indexer in it:
    ``attend(q, ckv, kpe, qi, w, kidx) -> o`` gets `index_project`'s
    three after the latent attention's."""
    return K.block(
        x, p, cfg, positions, attend, valid, tiled,
        indexer=lambda u, cq, cos, sin: index_project(
            u, cq, p["indexer"], cfg, cos, sin))


def selection(qi, w, kidx, ok, cfg: GlmDsaConfig):
    """Every query's selection over a whole score matrix (a short
    sequence: the full forward, a dense prefill): qi (B, T, J, D), w
    (B, T, J), kidx (B, S, D), `ok` (B, T, S) what each query may
    reach -> (B, T, S) bool, inside `ok`."""
    return dsa.select_mask(dsa.index_scores(qi, w, kidx), ok,
                           cfg.index_topk)


def glm_dsa_hidden(params, tokens, cfg: GlmDsaConfig, rules=DEFAULT_RULES):
    """tokens (B, T) -> (final hidden (B, T, d), expert stats): the
    full-sequence forward, causal and selected, no cache."""
    B, T = tokens.shape
    positions = jnp.arange(T, dtype=jnp.int32)[None]
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), bool))[None],
                              (B, T, T))
    x = with_logical_constraint(embed(params, tokens, cfg),
                                ("batch", "seq", "embed"), rules)

    def layer(x, carry, p, lidx):
        def attend(q, ckv, kpe, qi, w, kidx):
            return K.attend_expanded(
                q, ckv, kpe, p["attn"],
                selection(qi, w, kidx, causal, cfg), cfg)

        x, stats = block(x, p, cfg, positions, attend, tiled=False)
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
        return x, carry, (), stats

    x, _, _, stats = K.walk_layers(cfg, params, x, (), layer)
    return x, stats


def glm_dsa_forward(params, tokens, cfg: GlmDsaConfig,
                    rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 -> logits (B, T, padded_vocab) float32."""
    hidden, _ = glm_dsa_hidden(params, tokens, cfg, rules)
    return with_logical_constraint(lm_logits(hidden, params, cfg),
                                   ("batch", "seq", "vocab"), rules)


def glm_dsa_loss(params, batch, cfg: GlmDsaConfig,
                 rules=DEFAULT_RULES) -> jnp.ndarray:
    """kimi_k2_loss over this family's forward (the selection is a
    mask: no gradient reaches the indexer through it, and nothing here
    trains)."""
    return K.kimi_k2_loss(params, batch, cfg, rules, forward=glm_dsa_forward)


__all__ = ["GlmDsaConfig", "glm_dsa_config", "glm_dsa_init",
           "glm_dsa_forward", "glm_dsa_loss", "glm_dsa_logical_axes",
           "glm_dsa_param_count"]
