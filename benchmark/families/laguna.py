"""Family ``laguna``: what the benchmark has to know of poolside's
Laguna block, from the keys of the published ``config.json``
(``families/gpt2.py``'s docstring lists what a family file holds).

A layer attends the whole context (``full_attention``) or the last
``sliding_window`` positions (``sliding_attention``), with its own count
of query heads (``num_attention_heads_per_layer``) over
``num_key_value_heads`` K/V heads of ``head_dim``; layer 0 ends in a
dense SwiGLU MLP, the others in ``num_experts`` small experts of which a
token takes ``num_experts_per_tok``, ALL held by this chip.  The
per-layer lists of the source stay whole in a configuration's file; the
first ``num_hidden_layers`` entries are the layers it runs.

The cache has two reaches: the full layers' K/V lie in the paged pool,
a window layer keeps ``sliding_window`` rows a slot
(``ray_tpu/models/laguna_decode.py``).  The harness's "K/V bytes a
token" (``kv_bytes_per_token``: what a block of the pool weighs) and
``attention_shape`` therefore describe the POOL: the full layers alone.
``window_bytes_per_slot`` is the other reach.

What a decode step must read depends on which experts its rows touch,
which ``decode_step_bytes``'s signature cannot know: it counts none of
them (a lower bound), and the readers take the touched experts from the
program's counter (``expert_bytes``, ``metrics/moe_expert_roofline.py``)
and the attention's bytes from the window's own waves
(``attn_decode_bytes``, ``metrics/attn_decode_roofline.py``).
"""

from __future__ import annotations

import types
from typing import Any, Dict, List

REFERENCE = "laguna"

_KINDS = {"full_attention": "full", "sliding_attention": "window"}


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as the keyword overrides the program's
    ``laguna_config`` takes.  ``max_seq`` is the context a replica is
    given: no weight depends on it, so a serving cell sets it in its
    traffic file as a server's ``max_model_len`` is set."""
    L = int(config["num_hidden_layers"])
    rope = config["rope_parameters"]
    full, window = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or window["rope_type"] != "default":
        raise SystemExit("family laguna: full layers rotate with yarn, "
                         "window layers unscaled")
    if config.get("moe_apply_router_weight_on_input"):
        raise SystemExit("family laguna: the program weighs an expert's "
                         "output, not its input")
    hd = int(config["head_dim"])
    d_expert = int(config["moe_intermediate_size"])
    return {"layer_types": tuple(_KINDS[t]
                                 for t in config["layer_types"][:L]),
            "heads_per_layer": tuple(
                int(h) for h in config["num_attention_heads_per_layer"][:L]),
            "mlp_types": tuple(config["mlp_layer_types"][:L]),
            "d_model": int(config["hidden_size"]), "head_dim": hd,
            "n_head": int(config["num_attention_heads"]),
            "n_kv_head": int(config["num_key_value_heads"]),
            "window": int(config["sliding_window"]),
            "d_ff": int(config["intermediate_size"]),
            "d_expert": d_expert,
            "n_routed": int(config["num_experts"]),
            "top_k": int(config["num_experts_per_tok"]),
            "n_shared": int(config["shared_expert_intermediate_size"])
            // d_expert,
            "route_scale": float(config["moe_routed_scaling_factor"]),
            "full_rotary_dim": int(round(
                hd * float(full["partial_rotary_factor"]))),
            "full_rope_theta": float(full["rope_theta"]),
            "rope_factor": float(full["factor"]),
            "rope_orig_max": int(full["original_max_position_embeddings"]),
            "beta_fast": float(full["beta_fast"]),
            "beta_slow": float(full["beta_slow"]),
            "attention_factor": float(full["attention_factor"]),
            "window_rope_theta": float(window["rope_theta"]),
            "rms_eps": float(config["rms_norm_eps"]),
            "max_seq": int(config["max_position_embeddings"]),
            "vocab_size": int(config["vocab_size"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models.laguna import (laguna_config, laguna_init,
                                       laguna_logical_axes, laguna_loss)

    cfg = laguna_config(config["program"]["preset"],
                        **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: laguna_init(key, cfg),
        loss=lambda params, batch: laguna_loss(params, batch, cfg),
        logical_axes=lambda: laguna_logical_axes(cfg))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree: each
    layer's kind, the K/V heads and head size (K and V are folded in
    the tree), the window, the rotary numbers of both kinds, the
    router's count and scale, the norm's epsilon."""
    s = sizes(config)
    keys = ("layer_types", "n_kv_head", "head_dim", "window", "top_k",
            "route_scale", "full_rotary_dim", "full_rope_theta",
            "rope_factor", "rope_orig_max", "beta_fast", "beta_slow",
            "attention_factor", "window_rope_theta")
    return dict({k: s[k] for k in keys}, eps=s["rms_eps"])


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """The near-tie tolerance a served answer is held to: 0.7.

    Not ``correct.logit_tie_tol(n_layer)`` (0.03 for five layers): that
    one was read off GPT-2's dense blocks.  Here, as for Kimi-K2
    (``families/kimi_k2.py``), the error has a heavy tail: the bf16
    residual stream moves the router's input, and where a token's 8th
    and 9th of 256 near-flat softmax scores swap, a whole expert's
    output (times 2.5 / 8) enters or leaves the token's hidden state.
    The untied head of N(0, 0.02) over a hidden of 2,048 gives logits
    of std 0.9, half of Kimi's 1.7.  Most tokens are the reference's
    own argmax (461 to 485 of 512); the few that are not lie up to 0.5
    under it.

    The readings (PERF.md section 4; my chip runs, PR 42), engine at
    the published widths, bf16 weights, answers of 512 tokens after
    prompts of 300 to 8,100 tokens, cold and after a prefix hit of 473
    to 506 blocks.  The engine's largest gap over 12 checked answers
    and 4 seeds: 0.23 to 0.48; over the 32 answers of the cell's own
    16 runs after them, 15 more seeds: 0.18 to 0.49.  The first
    answers held to the reference
    computed with its weights rounded to fp8 (e4m3, the nearest
    precision below the bf16 the configuration states), 8 answers, 2
    seeds: 0.94 to 1.35, with 210 to 273 of 512 tokens its argmax: not
    correct.  0.7 stands at 1.4 times the first and three quarters of
    the second, with the more room above the engine's reading, since
    fresh seeds read higher.  What would fail it beside lower
    precision: a window mask dropped, the gate left out or sigmoid
    weights move a logit by more than the head's spread
    (tests/test_laguna.py test_a_wrong_model_fails_the_tolerance holds
    each, on the CPU in float32, to 1e-5).  What it cannot see: a
    router fed bf16 inputs (its flips are the bf16 residual stream's
    own kind and size); the same test holds the router float32."""
    return 0.7


def _attn_params(config: Dict[str, Any], heads: int) -> int:
    """One layer's attention at `heads` query heads: q and o, k and v,
    the per-head gate."""
    s = sizes(config)
    d, hd = s["d_model"], s["head_dim"]
    return 2 * d * heads * hd + 2 * d * s["n_kv_head"] * hd + d * heads


def expert_params(config: Dict[str, Any]) -> int:
    """One routed (or shared) expert: 3 x 2,048 x 512 = 3,145,728."""
    s = sizes(config)
    return 3 * s["d_model"] * s["d_expert"]


def layer_params(config: Dict[str, Any]) -> List[int]:
    """Each layer's parameters: attention, two norms, and the dense MLP
    (layer 0: 79,794,176) or the router (weights and the selection
    bias the program's tree keeps at zero), the shared and the 256
    routed experts (a window layer 846,860,544, a full one
    838,439,168)."""
    s = sizes(config)
    d = s["d_model"]
    out = []
    for heads, mlp in zip(s["heads_per_layer"], s["mlp_types"]):
        ffn = 3 * d * s["d_ff"] if mlp == "dense" else (
            d * s["n_routed"] + s["n_routed"]
            + (s["n_shared"] + s["n_routed"]) * expert_params(config))
        out.append(_attn_params(config, heads) + 2 * d + ffn)
    return out


def param_count(config: Dict[str, Any]) -> int:
    """Embedding and untied head, the final norm, the layers:
    3,869,858,816 for the cell's layers 0-4."""
    s = sizes(config)
    return 2 * s["vocab_size"] * s["d_model"] + s["d_model"] \
        + sum(layer_params(config))


def _sparse_layers(config: Dict[str, Any]) -> int:
    return sum(m == "sparse" for m in sizes(config)["mlp_types"])


def _routed_params(config: Dict[str, Any]) -> int:
    return _sparse_layers(config) * sizes(config)["n_routed"] \
        * expert_params(config)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per parameter a token multiplies: everything but the embedding
    (a lookup) and the routed experts, of which a token meets ``top_k``
    a layer; plus attention's two products, a causal triangle on a full
    layer and a band of ``window`` on a window layer."""
    s = sizes(config)
    dense = param_count(config) - s["vocab_size"] * s["d_model"] \
        - _routed_params(config)
    routed = _sparse_layers(config) * s["top_k"] * expert_params(config)
    attn = sum(
        6.0 * heads * s["head_dim"]
        * (seq if kind == "full" else 2 * min(seq, s["window"]))
        for kind, heads in zip(s["layer_types"], s["heads_per_layer"]))
    return 6.0 * (dense + routed) + attn


def _row_bytes(config: Dict[str, Any], itemsize: int) -> int:
    """K and V of one token of one layer: 8 x 128 x 2 x 2 B = 4,096."""
    s = sizes(config)
    return 2 * s["n_kv_head"] * s["head_dim"] * itemsize


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """What one token weighs in the POOL: K and V through the full
    layers alone, 2 x 4,096 = 8,192 B for the cell's layers 0 and 4.  A
    window layer's rows are per slot (``window_bytes_per_slot``)."""
    return sizes(config)["layer_types"].count("full") \
        * _row_bytes(config, itemsize)


def window_bytes_per_slot(config: Dict[str, Any], itemsize: int = 2) -> int:
    """What one slot's rings weigh: ``sliding_window`` rows of K and V a
    window layer, 3 x 512 x 4,096 = 6,291,456 B, whatever the
    context."""
    s = sizes(config)
    return s["layer_types"].count("window") * s["window"] \
        * _row_bytes(config, itemsize)


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The K/V POOL: the full layers (``n_layer`` 2 of the cell's 5),
    ``n_kv_head`` K/V heads of ``head_dim``; ``n_head`` is a full
    layer's query heads."""
    s = sizes(config)
    full = [h for h, t in zip(s["heads_per_layer"], s["layer_types"])
            if t == "full"]
    return {"n_head": full[0] if full else s["heads_per_layer"][0],
            "n_kv_head": s["n_kv_head"], "head_dim": s["head_dim"],
            "n_layer": len(full), "d_model": s["d_model"]}


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """A LOWER bound of the HBM bytes one decode step needs: every
    weight that every row meets (all but the embedding's rows, which
    are looked up, and the routed experts, of which a step reads those
    its rows chose: none is counted here, the signature has no rows)
    and the full layers' K/V of each position attended (the window
    layers' rows, at most ``sliding_window`` a row, are left out: the
    signature has no rows either)."""
    s = sizes(config)
    always = param_count(config) - s["vocab_size"] * s["d_model"] \
        - _routed_params(config)
    return always * itemsize \
        + kv_bytes_per_token(config, itemsize) * positions_attended


def expert_bytes(config: Dict[str, Any], touched_share: float,
                 itemsize: int = 2) -> float:
    """HBM bytes of the routed experts one step reads where
    `touched_share` of the experts have a token, over the expert
    layers: 4 x 256 x 6.29 MB x share."""
    return _routed_params(config) * touched_share * itemsize


def expert_flops(config: Dict[str, Any], assignments: float) -> float:
    """The grouped matmuls' operations for `assignments` (token,
    expert) pairs: three products of 2,048 x 512."""
    return 2.0 * assignments * expert_params(config)


def attn_decode_bytes(config: Dict[str, Any], contexts,
                      itemsize: int = 2) -> float:
    """HBM bytes the attention of one decode step needs, from the
    published sizes alone, whatever implements it: every layer's
    attention weights once, and for each row its K and V, ``context``
    positions of each full layer and ``min(context, sliding_window)`` of
    each window layer.  `contexts`: the rows' context lengths."""
    s = sizes(config)
    weights = sum(_attn_params(config, h) for h in s["heads_per_layer"])
    row = _row_bytes(config, itemsize)
    n_full = s["layer_types"].count("full")
    n_window = s["layer_types"].count("window")
    return weights * itemsize + sum(
        row * (n_full * c + n_window * min(c, s["window"]))
        for c in contexts)


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's Laguna decode step
    and paged prefill (a prefill that leaves a snapshot: `state`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.laguna_decode import (laguna_decode_step,
                                              laguna_init_paged_cache,
                                              laguna_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = laguna_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k,
                state):
        logits, cache = laguna_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot, state=state)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: laguna_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key, i32(3)))]
