"""Family ``solar_open2``: what the benchmark has to know of Upstage's
Solar-Open2 block, from the keys of the published ``config.json``
(``families/gpt2.py``'s docstring lists what a family file holds).

Layer ``i`` of ``num_hidden_layers`` is a gated softmax layer without
positions (``num_attention_heads`` query heads over
``num_key_value_heads`` K/V heads of ``head_dim``) iff ``i`` is in
``gqa_layers``, else a KDA layer (``linear_attn_config``: a gated delta
rule whose state is a float32 matrix of ``head_dim`` x ``head_dim`` a
head, behind three convolutions of ``short_conv_kernel_size``); every
layer ends in ``n_routed_experts`` experts of which a token takes
``num_experts_per_tok``, and one shared expert.  ``gqa_layers`` stays
whole in a configuration's file; its entries below
``num_hidden_layers`` are the softmax layers it runs.  A configuration
states the CHIP'S SHARE of a deployment (``model-configs`` guide,
section 4), as ``families/kimi_k2.py`` reads it: ``n_routed_experts`` is
the number of experts this chip HOLDS (the first so many) and
``reduced_from.n_routed_experts`` the number the router scores,
likewise ``vocab_size`` and ``num_hidden_layers``.

Two kinds of cache follow: K/V for the softmax layers alone, and per
SEQUENCE, not per token, the matrices and the convolutions' windows of
each KDA layer.  The harness's "K/V bytes a token"
(``kv_bytes_per_token``: what a block of the pool weighs) and
``attention_shape`` therefore describe the POOL, one layer in four;
what the state costs is stated apart (``state_bytes_per_slot``) and
read by the metrics this family brings (``metrics/linear_*.py``:
``linear_decode_bytes``, ``linear_prefill_flops``).  The touched
experts' bytes and the attention's are counted as
``families/laguna.py`` counts them (``expert_bytes``,
``attn_decode_bytes``).
"""

from __future__ import annotations

import types
from typing import Any, Dict, List

REFERENCE = "solar_open2"


def _published(config: Dict[str, Any], key: str) -> int:
    return int((config.get("reduced_from") or {}).get(key, config[key]))


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as the keyword overrides the program's
    ``solar_open2_config`` takes.  ``max_seq`` is the context a replica
    is given: no weight depends on it (the model has no positions), so
    a serving cell sets it in its traffic file as a server's
    ``max_model_len`` is set.  ``gate_rank`` is not a key of the
    source: the low-rank pairs' rank is taken as the KDA head size
    (``assumed.kda_use_full_proj`` in the configuration's file)."""
    lin = config["linear_attn_config"]
    if config["use_rope"] or not config["use_gqa_gate"]:
        raise SystemExit("family solar_open2: the program's softmax layer "
                         "has no rotary and a gate")
    if config["kda_use_full_proj"]:
        raise SystemExit("family solar_open2: the program's KDA gates are "
                         "low-rank pairs (kda_use_full_proj false)")
    if int(config["first_k_dense_replace"]):
        raise SystemExit("family solar_open2: every layer of the program "
                         "ends in the expert layer")
    if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
        raise SystemExit("family solar_open2: a KDA layer's keys and "
                         "values have its queries' heads")
    return {"n_layer": int(config["num_hidden_layers"]),
            "gqa_layers": tuple(int(i) for i in config["gqa_layers"]),
            "d_model": int(config["hidden_size"]),
            "n_head": int(config["num_attention_heads"]),
            "n_kv_head": int(config["num_key_value_heads"]),
            "head_dim": int(config["head_dim"]),
            "kda_heads": int(lin["num_heads"]),
            "kda_head_dim": int(lin["head_dim"]),
            "d_conv": int(lin["short_conv_kernel_size"]),
            "gate_rank": int(lin["head_dim"]),
            "neg_eigval": bool(config["kda_allow_neg_eigval"]),
            "d_expert": int(config["moe_intermediate_size"]),
            "n_routed": _published(config, "n_routed_experts"),
            "held": tuple(range(int(config["n_routed_experts"]))),
            "top_k": int(config["num_experts_per_tok"]),
            "n_shared": int(config["n_shared_experts"]),
            "norm_topk": bool(config["norm_topk_prob"]),
            "route_scale": float(config["routed_scaling_factor"]),
            "rms_eps": float(config["rms_norm_eps"]),
            "max_seq": int(config["max_position_embeddings"]),
            "vocab_size": int(config["vocab_size"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models.solar_open2 import (solar_open2_config,
                                            solar_open2_init,
                                            solar_open2_logical_axes,
                                            solar_open2_loss)

    cfg = solar_open2_config(config["program"]["preset"],
                             **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: solar_open2_init(key, cfg),
        loss=lambda params, batch: solar_open2_loss(params, batch, cfg),
        logical_axes=lambda: solar_open2_logical_axes(cfg))


def layer_types(config: Dict[str, Any]) -> List[str]:
    """"gqa" or "kda" for each layer the configuration runs."""
    s = sizes(config)
    return ["gqa" if i in s["gqa_layers"] else "kda"
            for i in range(s["n_layer"])]


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree: each
    layer's kind, the K/V heads and head size (K and V are folded in
    the tree), whether ``beta`` is doubled, and the router's (which
    experts the stacked weights are, a token's count of them, the
    scale), the norm's epsilon."""
    s = sizes(config)
    keys = ("n_kv_head", "head_dim", "neg_eigval", "held", "top_k",
            "norm_topk", "route_scale")
    return dict({k: s[k] for k in keys}, eps=s["rms_eps"],
                layer_types=tuple(layer_types(config)))


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """The near-tie tolerance a served answer is held to: 0.8.

    Not ``correct.logit_tie_tol(n_layer)`` (0.03 for four layers): that
    one was read off GPT-2's dense blocks.  Here, as for Kimi-K2 and
    Laguna (``families/kimi_k2.py``, ``families/laguna.py``), the error
    has a heavy tail: the bf16 residual stream moves the router's input,
    and where a token's 8th and 9th of 320 sigmoid scores swap and one
    of the two is an expert this chip holds, a whole expert's output
    enters or leaves the token's hidden state.  The untied head of N(0,
    0.02) over a hidden of 4,096 gives logits of std 1.28, between
    Laguna's 0.9 (limit 0.7) and Kimi's 1.7 (limit 1.2).

    The readings (PERF.md section 4; my chip runs, PR 49, on the draw
    `solar_open2_init` makes now), engine at the published widths, bf16
    weights, float32 state, answers of 512 tokens after a prompt of
    8,030 tokens, cold and after a prefix hit of 501 blocks from a
    snapshot of the matrices.  The engine's largest gap over 12 checked
    answers and 6 seeds: 0.18 to 0.38, with 445 to 483 of 512 tokens
    the reference's own argmax (under the first draw, 44 answers and 22
    seeds: 0.20 to 0.47).  The same answers held to the reference
    computed with every weight matrix rounded to fp8 (e4m3, the nearest
    precision below the bf16 the configuration states; rounded where it
    is used: a rounded tree does not fit beside the engine): 1.29 and
    1.18, with 269 and 278 of 512 its argmax: not correct.  A chunked
    delta rule that drops its ``K+ S_0`` term (the chunk's right-hand
    side as if the state entering were zero): 4.31 and 4.02, 189 and
    183 of 512: not correct.  0.8 stands at 2.1 times the first and two
    thirds of the second, with the more room above the engine's reading,
    since fresh seeds read higher.  What it cannot see:
    tests/test_solar_open2.py holds, on the CPU in float32 at 2e-5,
    what a largest gap hears faintly (the matrices rounded to bf16 a
    step, beta left in (0, 1), the softmax layer's gate left out)."""
    return 0.8


def gqa_params(config: Dict[str, Any]) -> int:
    """One softmax layer's attention: q, o and the per-channel gate of
    4,096 x 8,192 each, k and v of 4,096 x 1,024: 109,051,904."""
    s = sizes(config)
    d, hd = s["d_model"], s["head_dim"]
    return 3 * d * s["n_head"] * hd + 2 * d * s["n_kv_head"] * hd


def kda_matmul_params(config: Dict[str, Any]) -> int:
    """What of one KDA layer every token multiplies: q, k, v and o
    (4 x 4,096 x 8,192), the two low-rank pairs (4,096 x 128 x 8,192
    each) and ``W_beta`` (4,096 x 64): 137,625,600."""
    s = sizes(config)
    d, w, r = s["d_model"], s["kda_heads"] * s["kda_head_dim"], \
        s["gate_rank"]
    return 4 * d * w + 2 * (d * r + r * w) + d * s["kda_heads"]


def kda_params(config: Dict[str, Any]) -> int:
    """One KDA layer's mixer: `kda_matmul_params`, the three
    convolutions (3 x 4 x 8,192), ``A_log`` (64), ``dt_bias`` (8,192)
    and the output norm (128): 137,732,288."""
    s = sizes(config)
    w = s["kda_heads"] * s["kda_head_dim"]
    return kda_matmul_params(config) + 3 * s["d_conv"] * w \
        + s["kda_heads"] + w + s["kda_head_dim"]


def expert_params(config: Dict[str, Any]) -> int:
    """One routed (or shared) expert: 3 x 4,096 x 1,280 = 15,728,640."""
    s = sizes(config)
    return 3 * s["d_model"] * s["d_expert"]


def layer_params(config: Dict[str, Any]) -> List[int]:
    """Each layer's parameters: its mixer, two norms, the router
    (weights and selection bias), the shared and the HELD experts: a
    softmax layer 755,245,376 and a KDA layer 783,925,760 with 40 of
    320 held."""
    s = sizes(config)
    d = s["d_model"]
    ffn = d * s["n_routed"] + s["n_routed"] \
        + (s["n_shared"] + len(s["held"])) * expert_params(config)
    mixer = {"gqa": gqa_params(config), "kda": kda_params(config)}
    return [mixer[t] + 2 * d + ffn for t in layer_types(config)]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    kinds = layer_types(config)
    return {"gqa": kinds.count("gqa"), "kda": kinds.count("kda")}


def param_count(config: Dict[str, Any]) -> int:
    """Embedding and untied head (the rows held), the final norm, the
    layers: 3,308,353,344 for the cell's layers 0-3, 40 experts held
    and 24,576 rows."""
    s = sizes(config)
    return 2 * s["vocab_size"] * s["d_model"] + s["d_model"] \
        + sum(layer_params(config))


def _routed_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return s["n_layer"] * len(s["held"]) * expert_params(config)


def _kda_flops_per_token(config: Dict[str, Any]) -> float:
    """The recurrence's own work a token a layer, whatever chunk size
    implements it: per head decay-and-project (``S'`` and ``S'^T k``),
    the rank-one update and the read-out, 2 hd^2 each."""
    s = sizes(config)
    return 6.0 * s["kda_head_dim"] ** 2 * s["kda_heads"]


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per parameter a token multiplies: everything but the embedding
    (a lookup) and the routed experts, of which a token meets ``top_k *
    held / n_routed`` on this chip; plus a softmax layer's causal
    triangle and a KDA layer's recurrence, forward and backward."""
    s, n = sizes(config), layer_counts(config)
    dense = param_count(config) - s["vocab_size"] * s["d_model"] \
        - _routed_params(config)
    routed = s["n_layer"] * s["top_k"] * len(s["held"]) / s["n_routed"] \
        * expert_params(config)
    mixing = n["gqa"] * 6.0 * s["n_head"] * s["head_dim"] * seq \
        + n["kda"] * 3.0 * _kda_flops_per_token(config)
    return 6.0 * (dense + routed) + mixing


def _row_bytes(config: Dict[str, Any], itemsize: int) -> int:
    """K and V of one token of one layer: 8 x 128 x 2 x 2 B = 4,096."""
    s = sizes(config)
    return 2 * s["n_kv_head"] * s["head_dim"] * itemsize


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """What one token weighs in the POOL: K and V through the softmax
    layers alone, 4,096 B for the cell's one.  A KDA layer's state is
    per slot (``state_bytes_per_slot``)."""
    return layer_counts(config)["gqa"] * _row_bytes(config, itemsize)


def state_bytes_per_slot(config: Dict[str, Any], state_itemsize: int = 4,
                         itemsize: int = 2) -> int:
    """One sequence's recurrent state through every KDA layer: 64
    matrices of 128 x 128 float32 (4,194,304 B) and the convolutions'
    windows (3 rows of 3 x 8,192 inputs, bf16: 147,456 B) a layer,
    13,025,280 B for the cell's three, whatever the context."""
    s = sizes(config)
    H, hd = s["kda_heads"], s["kda_head_dim"]
    return layer_counts(config)["kda"] * (
        H * hd * hd * state_itemsize
        + (s["d_conv"] - 1) * 3 * H * hd * itemsize)


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The K/V POOL: the softmax layers (``n_layer`` 1 of the cell's
    4), ``n_kv_head`` K/V heads of ``head_dim``."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": s["n_kv_head"],
            "head_dim": s["head_dim"],
            "n_layer": layer_counts(config)["gqa"],
            "d_model": s["d_model"]}


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """A LOWER bound of the HBM bytes one decode step needs: every
    weight that every row meets (all but the embedding's rows, which
    are looked up, and the routed experts, of which a step reads those
    its rows chose) and the softmax layers' K/V of each position
    attended.  The signature has no rows: neither the touched experts
    (``expert_bytes``) nor the rows' matrices (``linear_decode_bytes``)
    are counted here."""
    s = sizes(config)
    always = param_count(config) - s["vocab_size"] * s["d_model"] \
        - _routed_params(config)
    return always * itemsize \
        + kv_bytes_per_token(config, itemsize) * positions_attended


def expert_bytes(config: Dict[str, Any], touched_share: float,
                 itemsize: int = 2) -> float:
    """HBM bytes of the routed experts one step reads where
    `touched_share` of the held experts have a token, over the layers:
    4 x 40 x 31.5 MB x share."""
    return _routed_params(config) * touched_share * itemsize


def expert_flops(config: Dict[str, Any], assignments: float) -> float:
    """The grouped matmuls' operations for `assignments` (token,
    expert) pairs on held experts: three products of 4,096 x 1,280."""
    return 2.0 * assignments * expert_params(config)


def attn_decode_bytes(config: Dict[str, Any], contexts,
                      itemsize: int = 2) -> float:
    """HBM bytes the softmax attention of one decode step needs, from
    the published sizes alone, whatever implements it: every softmax
    layer's attention weights once, and for each row its K and V,
    ``context`` positions a layer.  `contexts`: the rows' context
    lengths."""
    n = layer_counts(config)["gqa"]
    return n * (gqa_params(config) * itemsize
                + _row_bytes(config, itemsize) * sum(contexts))


def linear_decode_bytes(config: Dict[str, Any], rows: float,
                        itemsize: int = 2) -> float:
    """HBM bytes the KDA mixers of one decode step need: their weights
    once, and each decoding row's matrices and windows read and written
    once."""
    return layer_counts(config)["kda"] * kda_params(config) * itemsize \
        + rows * 2 * state_bytes_per_slot(config, itemsize=itemsize)


def linear_prefill_flops(config: Dict[str, Any], tokens: float) -> float:
    """Operations the KDA mixers need to prefill `tokens` tokens: 2 per
    matmul parameter a token, and the recurrence's own work
    (`_kda_flops_per_token`): the least, the same whatever chunk size
    implements it (a chunked form's triangular solve and pairwise
    decays are its own overhead, not counted)."""
    return tokens * layer_counts(config)["kda"] * (
        2.0 * kda_matmul_params(config) + _kda_flops_per_token(config))


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's Solar-Open2 decode
    step and paged prefill (with its `state` argument, as the engine
    calls it)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.solar_open2_decode import (
        solar_open2_decode_step, solar_open2_init_paged_cache,
        solar_open2_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = solar_open2_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k,
                state):
        logits, cache = solar_open2_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot, state=state)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: solar_open2_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key, i32(3)))]
