"""Family ``kimi_k2``: what the benchmark has to know of Moonshot's
Kimi-K2 block (DeepSeek-V3's at other numbers), from the keys of the
published ``config.json`` (``families/gpt2.py``'s docstring lists what
a family file holds).

Every layer attends through latent attention (MLA): a token leaves per
layer ONE latent of ``kv_lora_rank`` and ONE rotary key of
``qk_rope_head_dim``, shared by all heads, so the harness's "K/V bytes a
token" are ``(512 + 64) * 2 B = 1,152 B`` a layer and
``attention_shape`` describes a pool of latents, not of heads.  Layer 0
(``first_k_dense_replace``) ends in a dense SwiGLU MLP, the others in a
sparse expert layer.  A configuration states the CHIP'S SHARE of a
deployment (``model-configs`` guide, section 4): ``n_routed_experts``
is the number of experts this chip HOLDS (the first so many) and
``reduced_from.n_routed_experts`` the number the router scores (the
published one), likewise ``vocab_size`` and ``num_hidden_layers``; a
key that ``reduced_from`` lacks is as published.

What a decode step must read depends on which experts its rows touch,
which ``decode_step_bytes``'s signature cannot know: it counts none of
them (a lower bound), and the readers this family brings take the
touched experts from the program's counter (``expert_bytes``,
``metrics/moe_expert_roofline.py``; ``mla_decode_bytes`` /
``mla_decode_flops``, ``metrics/mla_decode_roofline.py``).
"""

from __future__ import annotations

import types
from typing import Any, Dict

REFERENCE = "kimi_k2"


def _published(config: Dict[str, Any], key: str) -> int:
    return int((config.get("reduced_from") or {}).get(key, config[key]))


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as the keyword overrides the program's
    ``kimi_k2_config`` takes.  ``max_seq`` is the context a replica is
    given: no weight depends on it, so a serving cell sets it in its
    traffic file as a server's ``max_model_len`` is set."""
    if config["scoring_func"] != "sigmoid" \
            or config["topk_method"] != "noaux_tc" \
            or int(config["n_group"]) != 1 or int(config["topk_group"]) != 1:
        raise SystemExit("family kimi_k2: the program's router is sigmoid "
                         "scores with a selection bias and no group limit")
    rope = config["rope_scaling"]
    if rope["type"] != "yarn":
        raise SystemExit("family kimi_k2: rope_scaling must be yarn")
    return {"n_layer": int(config["num_hidden_layers"]),
            "n_dense": int(config["first_k_dense_replace"]),
            "n_head": int(config["num_attention_heads"]),
            "d_model": int(config["hidden_size"]),
            "q_lora_rank": int(config["q_lora_rank"]),
            "kv_lora_rank": int(config["kv_lora_rank"]),
            "qk_nope_dim": int(config["qk_nope_head_dim"]),
            "qk_rope_dim": int(config["qk_rope_head_dim"]),
            "v_head_dim": int(config["v_head_dim"]),
            "d_ff": int(config["intermediate_size"]),
            "d_expert": int(config["moe_intermediate_size"]),
            "n_routed": _published(config, "n_routed_experts"),
            "held": tuple(range(int(config["n_routed_experts"]))),
            "top_k": int(config["num_experts_per_tok"]),
            "n_shared": int(config["n_shared_experts"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "rope_theta": float(config["rope_theta"]),
            "rope_factor": float(rope["factor"]),
            "rope_orig_max": int(rope["original_max_position_embeddings"]),
            "beta_fast": float(rope["beta_fast"]),
            "beta_slow": float(rope["beta_slow"]),
            "mscale": float(rope["mscale"]),
            "mscale_all_dim": float(rope["mscale_all_dim"]),
            "rms_eps": float(config["rms_norm_eps"]),
            "max_seq": int(config["max_position_embeddings"]),
            "vocab_size": int(config["vocab_size"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models.kimi_k2 import (kimi_k2_config, kimi_k2_init,
                                        kimi_k2_logical_axes, kimi_k2_loss)

    cfg = kimi_k2_config(config["program"]["preset"],
                         **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: kimi_k2_init(key, cfg),
        loss=lambda params, batch: kimi_k2_loss(params, batch, cfg),
        logical_axes=lambda: kimi_k2_logical_axes(cfg))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree: the norm's
    epsilon, the head split, the rotary numbers, and the router's
    (which experts the stacked weights are, a token's count of them,
    the scale)."""
    s = sizes(config)
    keys = ("qk_nope_dim", "qk_rope_dim", "held", "top_k", "norm_topk",
            "route_scale", "rope_theta", "rope_factor", "rope_orig_max",
            "beta_fast", "beta_slow", "mscale", "mscale_all_dim")
    return dict({k: s[k] for k in keys}, eps=s["rms_eps"])


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """The near-tie tolerance a served answer is held to: 1.2.

    Not ``correct.logit_tie_tol(n_layer)`` (0.03 for six layers): that
    one was read off GPT-2.  Here the untied head of N(0, 0.02) over a
    hidden of 7,168 gives logits of std 1.7, and the error has a heavy
    tail that no dense block has: the bf16 residual stream moves the
    router's input, and where a token's 8th and 9th of 384 scores swap
    and one of the two is an expert this chip holds, a whole expert's
    output enters or leaves the token's hidden state.  Most tokens are
    the reference's own argmax (491 to 502 of 512); the few that are
    not lie up to 0.7 under it.

    The readings (PERF.md section 4; my chip runs, PR 32), engine at
    the published widths, bf16 weights, answers of 512 tokens after
    prompts of 6,000 to 8,167 tokens.  The engine's largest gap over 38
    checked answers and 21 seeds: 0.05 to 0.71 (twelve of them read
    with an earlier draw of the selection bias: 0.17 to 0.71; the 26 of
    the final tree: 0.05 to 0.64); with the router's input and weights
    rounded to bf16, 0.18 (the limit does not see a bf16 router: 99.68%
    of 1.55 M routing choices agree with the float32 router's on the
    same input, and 32 choices of a held expert were
    lost).  Weights rounded to fp8 through the same programs: 1.57,
    1.65 and 1.87, with 259 to 290 of 512 tokens the reference's
    argmax: not correct.  1.2 stands at 1.7 times the first and three quarters of
    the second, with the more room above the engine's reading, since
    fresh seeds read higher.  What it cannot see: the latent and rotary
    key cached in fp8 read 0.52 (439 of 512 identical), inside the
    engine's own range: a largest gap hears one flipped expert louder
    than every cached value rounded; tests/test_mla.py holds the cache
    exact, on the CPU in float32, where prefill then decode through the
    pool equal the full forward to 1e-5."""
    return 1.2


def mla_params(config: Dict[str, Any]) -> int:
    """One layer's latent attention: q_a, its norm, q_b, kv_a, its
    norm, kv_b (as W_uk and W_uv), o: 101,124,096 for Kimi-K2."""
    s = sizes(config)
    d, H = s["d_model"], s["n_head"]
    qk = s["qk_nope_dim"] + s["qk_rope_dim"]
    return (d * s["q_lora_rank"] + s["q_lora_rank"]
            + s["q_lora_rank"] * H * qk
            + d * (s["kv_lora_rank"] + s["qk_rope_dim"]) + s["kv_lora_rank"]
            + s["kv_lora_rank"] * H * (s["qk_nope_dim"] + s["v_head_dim"])
            + H * s["v_head_dim"] * d)


def expert_params(config: Dict[str, Any]) -> int:
    """One routed (or shared) expert: 3 x 7,168 x 2,048 = 44,040,192."""
    s = sizes(config)
    return 3 * s["d_model"] * s["d_expert"]


def layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """A dense layer (497,500,160) and an expert layer with this chip's
    experts (676,413,824 with 12 of 384 held): MLA, two norms, and the
    MLP, or router (weights and selection bias), shared and held
    experts."""
    s = sizes(config)
    d = s["d_model"]
    base = mla_params(config) + 2 * d
    return {"dense": base + 3 * d * s["d_ff"],
            "expert": base + d * s["n_routed"] + s["n_routed"]
            + (s["n_shared"] + len(s["held"])) * expert_params(config)}


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    s = sizes(config)
    return {"dense": s["n_dense"], "expert": s["n_layer"] - s["n_dense"]}


def param_count(config: Dict[str, Any]) -> int:
    """Embedding and untied head (the rows held), the final norm, the
    layers: 4,173,177,728 for the cell's 1 + 5 layers, 12 experts held
    and 20,480 rows."""
    s, n, per = sizes(config), layer_counts(config), layer_params(config)
    return (2 * s["vocab_size"] * s["d_model"] + s["d_model"]
            + n["dense"] * per["dense"] + n["expert"] * per["expert"])


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per parameter a token multiplies: everything but the embedding
    (a lookup) and the routed experts, of which a token meets
    ``top_k * held / n_routed`` on this chip; plus causal attention's
    two T x T products at head widths 192 and 128."""
    s, n = sizes(config), layer_counts(config)
    dense = param_count(config) - s["vocab_size"] * s["d_model"] \
        - n["expert"] * len(s["held"]) * expert_params(config)
    routed = n["expert"] * s["top_k"] * len(s["held"]) / s["n_routed"] \
        * expert_params(config)
    attn = 3.0 * s["n_layer"] * seq * s["n_head"] * (
        s["qk_nope_dim"] + s["qk_rope_dim"] + s["v_head_dim"])
    return 6.0 * (dense + routed) + attn


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """The latent and the rotary key of one token through every layer:
    1,152 B a layer, 6,912 B for the cell's six."""
    s = sizes(config)
    return s["n_layer"] * (s["kv_lora_rank"] + s["qk_rope_dim"]) * itemsize


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The latent pool: per token and layer one row of ``latent_dim``
    (``kv_lora_rank`` + ``qk_rope_head_dim``) for ALL heads
    (``n_kv_head`` 1); ``head_dim`` is the queries' and keys' (192),
    ``v_head_dim`` the values'."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": 1,
            "head_dim": s["qk_nope_dim"] + s["qk_rope_dim"],
            "v_head_dim": s["v_head_dim"],
            "latent_dim": s["kv_lora_rank"] + s["qk_rope_dim"],
            "n_layer": s["n_layer"], "d_model": s["d_model"]}


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """A LOWER bound of the HBM bytes one decode step needs: every
    weight that every row meets (all but the embedding's rows, which
    are looked up, and the routed experts, of which a step reads those
    its rows chose: none is counted here, the signature has no rows)
    and the latents of each position attended."""
    s, n = sizes(config), layer_counts(config)
    always = param_count(config) - s["vocab_size"] * s["d_model"] \
        - n["expert"] * len(s["held"]) * expert_params(config)
    return always * itemsize \
        + kv_bytes_per_token(config, itemsize) * positions_attended


def expert_bytes(config: Dict[str, Any], touched_share: float,
                 itemsize: int = 2) -> float:
    """HBM bytes of the routed experts one step reads where
    `touched_share` of the held experts have a token, over the expert
    layers."""
    n = layer_counts(config)
    return n["expert"] * len(sizes(config)["held"]) * touched_share \
        * expert_params(config) * itemsize


def expert_flops(config: Dict[str, Any], assignments: float) -> float:
    """The grouped matmuls' operations for `assignments` (token,
    expert) pairs on held experts: three products of 7,168 x 2,048."""
    return 2.0 * assignments * expert_params(config)


def mla_decode_bytes(config: Dict[str, Any], positions_attended: float,
                     itemsize: int = 2) -> float:
    """HBM bytes the attention of one decode step needs: every layer's
    MLA weights once and the latents of each position attended."""
    return sizes(config)["n_layer"] * mla_params(config) * itemsize \
        + kv_bytes_per_token(config, itemsize) * positions_attended


def mla_decode_flops(config: Dict[str, Any], rows: float,
                     positions_attended: float) -> float:
    """Operations of the ABSORBED path for `rows` decoding rows: the
    projections (2 per weight a row), and per position attended and
    head a score over latent + rotary key and a weighted sum of the
    latent."""
    s = sizes(config)
    per_pos = 2.0 * s["n_head"] * (
        2 * s["kv_lora_rank"] + s["qk_rope_dim"])
    return s["n_layer"] * (2.0 * rows * mla_params(config)
                           + per_pos * positions_attended)


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's Kimi-K2 decode step
    and paged prefill."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.kimi_k2_decode import (kimi_k2_decode_step,
                                               kimi_k2_init_paged_cache,
                                               kimi_k2_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = kimi_k2_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k):
        logits, cache = kimi_k2_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: kimi_k2_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key))]
