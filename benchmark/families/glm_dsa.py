"""Family ``glm_dsa``: what the benchmark has to know of Z.ai's GLM-5
block (``model_type: glm_moe_dsa``: DeepSeek-V3's block with DeepSeek
Sparse Attention in it), from the keys of the published ``config.json``
(``families/gpt2.py``'s docstring lists what a family file holds).

The block is ``families/kimi_k2.py``'s at other numbers, and what is
the same arithmetic is ASKED of that file, not written again
(`mla_params`, `expert_params`, `expert_bytes`, `expert_flops`): latent
attention (MLA) with a query latent, a sigmoid ``noaux_tc`` router over
routed experts and one shared expert, leading dense layers.  What it
adds is a second, learned attention a layer, the *lightning indexer*:
``index_n_heads`` queries of ``index_head_dim`` a token, projected from
the query latent, score ONE cached key of ``index_head_dim`` a position,
and the latent attention reads only the ``index_topk`` positions of
highest score.  A token therefore leaves per layer a latent of
``kv_lora_rank``, a rotary key of ``qk_rope_head_dim`` AND an index key
of ``index_head_dim``: ``(512 + 64 + 128) * 2 B = 1,408 B`` a layer.

A decode step's attention reads EVERY position's index key (256 B a
layer) and only ``min(context, index_topk)`` positions' latents (1,152
B a layer): `sparse_decode_bytes` / `sparse_decode_flops`
(``metrics/sparse_attn_decode_roofline.py``).  ``mla_decode_bytes`` is
NOT stated: its reader counts every position of a context as attended,
which this family's attention never does; a family whose count of
positions differs overrides the metric, it does not join it.  A prefill
must score every (query, reachable key) pair whatever it then attends:
`index_prefill_flops` (``metrics/index_prefill_roofline.py``).

A configuration states the CHIP'S SHARE of a deployment (``model-configs``
guide, section 4), as ``families/kimi_k2.py`` reads it:
``n_routed_experts`` is the number of experts this chip HOLDS and
``reduced_from.n_routed_experts`` the number the router scores, likewise
``vocab_size``, ``num_hidden_layers`` and ``first_k_dense_replace``.
"""

from __future__ import annotations

import os
import types
from typing import Any, Dict

from benchmark import cells

REFERENCE = "glm_dsa"

#: ``families/kimi_k2.py`` of the tree this file lies in
_kimi = cells.load_family("kimi_k2", os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _as_kimi(config: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration under the keys ``families/kimi_k2.py`` reads
    its rotary numbers by: plain RoPE is YaRN at factor 1."""
    rope = config["rope_parameters"]
    return dict(config, rope_theta=rope["rope_theta"], rope_scaling={
        "type": "yarn", "factor": 1, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings":
            config["max_position_embeddings"]})


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as the keyword overrides the program's
    ``glm_dsa_config`` takes: ``families/kimi_k2.py``'s, the rotary
    scaling left at its plain default, and the indexer's three.
    ``max_seq`` is the context a replica is given (the traffic file's
    ``config_overrides``)."""
    if config["rope_parameters"]["rope_type"] != "default":
        raise SystemExit("family glm_dsa: the program's rotary positions "
                         "are plain (rope_type default)")
    if int(config["qk_head_dim"]) != int(config["qk_nope_head_dim"]) \
            + int(config["qk_rope_head_dim"]):
        raise SystemExit("family glm_dsa: qk_head_dim is not the sum of "
                         "its two parts")
    s = _kimi.sizes(_as_kimi(config))
    for key in ("rope_factor", "rope_orig_max", "beta_fast", "beta_slow",
                "mscale", "mscale_all_dim"):
        del s[key]
    return dict(s, index_n_heads=int(config["index_n_heads"]),
                index_head_dim=int(config["index_head_dim"]),
                index_topk=int(config["index_topk"]))


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models.glm_dsa import (glm_dsa_config, glm_dsa_init,
                                        glm_dsa_logical_axes, glm_dsa_loss)

    cfg = glm_dsa_config(config["program"]["preset"],
                         **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: glm_dsa_init(key, cfg),
        loss=lambda params, batch: glm_dsa_loss(params, batch, cfg),
        logical_axes=lambda: glm_dsa_logical_axes(cfg))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree: the norm's
    epsilon, the head split, the rotary base, how many positions a query
    attends, and the router's numbers."""
    s = sizes(config)
    keys = ("qk_nope_dim", "qk_rope_dim", "held", "top_k", "norm_topk",
            "route_scale", "rope_theta", "index_topk")
    return dict({k: s[k] for k in keys}, eps=s["rms_eps"])


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """The near-tie tolerance a served answer is held to: 1.7.

    Not ``correct.logit_tie_tol(n_layer)`` (0.03 for five layers), and
    wider than ``families/kimi_k2.py``'s 1.2 for the same block.  The
    untied head of N(0, 0.02) over a hidden of 6,144 gives logits of
    deviation 1.57, and the error has two heavy tails.  The router's, as
    Kimi-K2's: where a token's 8th and 9th of 256 scores swap in the
    bf16 stream and one of the two is an expert this chip holds, a
    WHOLE expert's output enters or leaves the token's hidden state.
    And the selection's own: bf16 index products move a score by 1.7e-3
    where neighbouring scores at the 2,048th place stand 1.6e-4 apart,
    so the program's selection and the float32 reference's differ at a
    few of 2,048 places a query; under seeded weights every attended
    row weighs alike and the values are uncorrelated, so those few
    places move an attention output by the square root of their share,
    and layer 0's attention stands before any FFN, a fifth of the first
    FFN's input.  About 415 of an answer's 512 tokens are the
    reference's own argmax (400 to 435), where Kimi-K2's are 491 to 502.

    The readings (PERF.md section 4; my chip runs, PR 58), engine at the
    published widths, bf16 weights, answers of 512 tokens after a
    prompt of 11,790 tokens, cold and again as a prefix hit over 736
    resident blocks.  The engine's largest gap over 40 checked answers
    and 20 seeds: 0.48 to 1.37 (median 0.80; 1.14, 1.22, 1.25 and 1.37
    the four highest, the last two of one seed).  The REFERENCE
    computed over weights rounded to fp8 (the nearest precision below
    the bf16 the configuration states), against the same engine: 1.82
    and 2.02, with 238 and 243 of 512 tokens its argmax: not correct.
    Attention over EVERY position instead of the selected 2,048: 5.90
    and 6.65 (18 of 512); an indexer without its ReLU: 4.05 and 4.77
    (66 and 70 of 512).  1.7 stands at 1.24 times the first and 0.93 of
    the second: the room above the engine's reading is the larger,
    since fresh seeds read higher (the tail above 0.9 falls by a factor
    of e every 0.2), and it is thin on both sides: a draw under which a
    bf16 selection's edge is quieter (an embedding that outweighs layer
    0's attention) would widen it, and is PERF.md section 7's."""
    return 1.7


def mla_params(config: Dict[str, Any]) -> int:
    """One layer's latent attention: 165,022,208 for GLM-5."""
    return _kimi.mla_params(_as_kimi(config))


def indexer_params(config: Dict[str, Any]) -> int:
    """One layer's indexer: its queries' projection from the query
    latent, its key's from the stream with a LayerNorm (weight and
    bias), its head weights': 9,371,904 for GLM-5."""
    s = sizes(config)
    J, D = s["index_n_heads"], s["index_head_dim"]
    return s["q_lora_rank"] * J * D + s["d_model"] * D + 2 * D \
        + s["d_model"] * J


def expert_params(config: Dict[str, Any]) -> int:
    """One routed (or shared) expert: 3 x 6,144 x 2,048 = 37,748,736."""
    return _kimi.expert_params(_as_kimi(config))


def layer_params(config: Dict[str, Any]) -> Dict[str, int]:
    """A dense layer (400,898,816) and an expert layer with this chip's
    experts (817,708,032 with 16 of 256 held; 213,728,256 outside its
    routed experts): MLA, the indexer, two norms, and the MLP, or router
    (weights and selection bias), shared and held experts."""
    s = sizes(config)
    d = s["d_model"]
    base = mla_params(config) + indexer_params(config) + 2 * d
    return {"dense": base + 3 * d * s["d_ff"],
            "expert": base + d * s["n_routed"] + s["n_routed"]
            + (s["n_shared"] + len(s["held"])) * expert_params(config)}


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    s = sizes(config)
    return {"dense": s["n_dense"], "expert": s["n_layer"] - s["n_dense"]}


def param_count(config: Dict[str, Any]) -> int:
    """Embedding and untied head (the rows held), the final norm, the
    layers: 3,909,632,768 for the cell's 1 + 4 layers, 16 experts held
    and 19,360 rows."""
    s, n, per = sizes(config), layer_counts(config), layer_params(config)
    return (2 * s["vocab_size"] * s["d_model"] + s["d_model"]
            + n["dense"] * per["dense"] + n["expert"] * per["expert"])


def _always(config: Dict[str, Any]) -> int:
    """The parameters every row of a step meets: all but the embedding's
    rows (looked up) and the routed experts (those some row chose)."""
    s, n = sizes(config), layer_counts(config)
    return param_count(config) - s["vocab_size"] * s["d_model"] \
        - n["expert"] * len(s["held"]) * expert_params(config)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """As ``families/kimi_k2.py`` counts it (no cell trains this
    family): 6 per parameter a token multiplies, the routed experts by
    the share a token meets on this chip, plus the index scores of the
    causal half and attention over at most ``index_topk`` positions."""
    s, n = sizes(config), layer_counts(config)
    routed = n["expert"] * s["top_k"] * len(s["held"]) / s["n_routed"] \
        * expert_params(config)
    attn = 3.0 * s["n_layer"] * (
        min(seq, 2 * s["index_topk"]) * s["n_head"] * (
            s["qk_nope_dim"] + s["qk_rope_dim"] + s["v_head_dim"])
        + seq * s["index_n_heads"] * s["index_head_dim"])
    return 6.0 * (_always(config) + routed) + attn


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """The latent, the rotary key and the index key of one token through
    every layer: 1,408 B a layer, 7,040 B for the cell's five."""
    s = sizes(config)
    return s["n_layer"] * (s["kv_lora_rank"] + s["qk_rope_dim"]
                           + s["index_head_dim"]) * itemsize


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The latent pool, as ``families/kimi_k2.py`` describes its own,
    and the indexer's: ``latent_dim`` is what the ATTENTION reads of a
    position (576), ``index_head_dim`` what the indexer reads (128).
    ``head_dim`` is the configuration's own (64: the source's config
    class calls the ROTARY width so, and nothing in MLA reads it); the
    queries' and keys' width is ``qk_head_dim`` (256)."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": 1,
            "head_dim": int(config["head_dim"]),
            "qk_head_dim": s["qk_nope_dim"] + s["qk_rope_dim"],
            "v_head_dim": s["v_head_dim"],
            "latent_dim": s["kv_lora_rank"] + s["qk_rope_dim"],
            "index_n_heads": s["index_n_heads"],
            "index_head_dim": s["index_head_dim"],
            "index_topk": s["index_topk"],
            "n_layer": s["n_layer"], "d_model": s["d_model"]}


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """A LOWER bound of the HBM bytes one decode step needs: every
    weight that every row meets and the INDEX key of each position of
    the rows' contexts.  The selected latents (the signature has no
    rows to cap them by) and the touched experts are not counted."""
    s = sizes(config)
    return _always(config) * itemsize + s["n_layer"] \
        * s["index_head_dim"] * itemsize * positions_attended


def expert_bytes(config: Dict[str, Any], touched_share: float,
                 itemsize: int = 2) -> float:
    return _kimi.expert_bytes(_as_kimi(config), touched_share, itemsize)


def expert_flops(config: Dict[str, Any], assignments: float) -> float:
    return _kimi.expert_flops(_as_kimi(config), assignments)


def _selected(config: Dict[str, Any], rows: float, positions: float,
              selected) -> float:
    """Positions a wave's attention reads: the caller's exact count
    (``min(context, index_topk)`` summed over its rows) where it has
    one, else the most that `rows` contexts of `positions` in all can
    select."""
    return min(positions, rows * sizes(config)["index_topk"]) \
        if selected is None else selected


def sparse_decode_bytes(config: Dict[str, Any], rows: float,
                        positions: float, selected=None,
                        itemsize: int = 2) -> float:
    """HBM bytes the attention of one decode step needs, indexer and
    all: every layer's MLA and indexer weights once, the index key of
    EVERY position of the rows' contexts (`positions`, summed over the
    rows) and the latent and rotary key of the positions selected."""
    s = sizes(config)
    return s["n_layer"] * itemsize * (
        mla_params(config) + indexer_params(config)
        + s["index_head_dim"] * positions
        + (s["kv_lora_rank"] + s["qk_rope_dim"])
        * _selected(config, rows, positions, selected))


def sparse_decode_flops(config: Dict[str, Any], rows: float,
                        positions: float, selected=None) -> float:
    """Operations of the same: the projections (2 per weight a row), per
    position and index head a product over ``index_head_dim``, and per
    position SELECTED and head the absorbed path's score over latent +
    rotary key and weighted sum of the latent."""
    s = sizes(config)
    return s["n_layer"] * (
        2.0 * rows * (mla_params(config) + indexer_params(config))
        + 2.0 * s["index_n_heads"] * s["index_head_dim"] * positions
        + 2.0 * s["n_head"] * (2 * s["kv_lora_rank"] + s["qk_rope_dim"])
        * _selected(config, rows, positions, selected))


def index_prefill_flops(config: Dict[str, Any], pairs: float) -> float:
    """Operations of a prefill's index scores for `pairs` (query,
    reachable key) pairs a layer: 2 x 32 x 128 a pair a layer, the same
    whatever tiles implement it and whatever the attention then
    skips."""
    s = sizes(config)
    return 2.0 * s["n_layer"] * s["index_n_heads"] * s["index_head_dim"] \
        * pairs


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's GLM-5 decode step
    and paged prefill."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.glm_dsa_decode import (glm_dsa_decode_step,
                                               glm_dsa_init_paged_cache,
                                               glm_dsa_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = glm_dsa_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k):
        logits, cache = glm_dsa_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: glm_dsa_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key))]
