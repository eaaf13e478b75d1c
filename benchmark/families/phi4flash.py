"""Family ``phi4flash``: what the benchmark has to know of Microsoft's
Phi-4-mini-flash (SambaY with differential attention), from the keys of
the published ``config.json`` (``families/gpt2.py``'s docstring lists
what a family file holds).

Of ``num_hidden_layers`` (32) layers, ``half = 16``: the even layers up
to ``half`` are Mamba (9), the odd ones below it attend a window of
``sliding_window`` (8), layer ``half + 1`` attends everything and keeps
the model's ONLY positional K/V; then the even layers are Gated Memory
Units over layer ``half``'s scan output (7) and the odd ones
cross-attention over layer ``half + 1``'s K/V (7).  Every layer ends in a
SwiGLU MLP.  What the source's ``config.json`` leaves to its class
defaults (the Mamba widths) is stated in the configuration's file under
``assumed.mamba`` and read from there.

Three kinds of cache follow (``ray_tpu/models/phi4flash_decode.py``):
the pool holds ONE layer's K/V, which eight layers read in a decode
step; a slot keeps the Mamba layers' state and the window layers'
rings.  The harness's "K/V bytes a token" (``kv_bytes_per_token``: what
a block of the pool weighs) and ``attention_shape`` therefore describe
the POOL: one layer, ``num_key_value_heads`` heads of ``head_dim``.
``window_bytes_per_slot`` and ``state_bytes_per_slot`` are the other
two.
"""

from __future__ import annotations

import math
import types
from typing import Any, Dict

REFERENCE = "phi4flash"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes, and the Mamba widths the file states under
    ``assumed.mamba``, as the keyword overrides the program's
    ``phi4flash_config`` takes.  ``max_seq`` is the context a replica is
    given: no weight depends on it (the model has no positions at all),
    so a serving cell sets it in its traffic file as a server's
    ``max_model_len`` is set, below the 262,144 the source allows."""
    if config.get("mlp_bias") or config.get("lm_head_bias"):
        raise SystemExit("family phi4flash: the program's MLP and head "
                         "have no bias")
    if not config["tie_word_embeddings"]:
        raise SystemExit("family phi4flash: the program ties the head to "
                         "the embedding")
    mamba = config["assumed"]["mamba"]
    if not mamba["conv_bias"] or mamba["proj_bias"]:
        raise SystemExit("family phi4flash: the program's mixer has a "
                         "convolution bias and no projection bias")
    d = int(config["hidden_size"])
    return {"n_layer": int(config["num_hidden_layers"]),
            "n_head": int(config["num_attention_heads"]),
            "n_kv_head": int(config["num_key_value_heads"]),
            "d_model": d, "d_ff": int(config["intermediate_size"]),
            "window": int(config["sliding_window"]),
            "mb_per_layer": int(config["mb_per_layer"]),
            "d_state": int(mamba["d_state"]), "d_conv": int(mamba["d_conv"]),
            "expand": int(mamba["expand"]),
            "dt_rank": int(mamba.get("dt_rank") or math.ceil(d / 16)),
            "ln_eps": float(config["layer_norm_eps"]),
            "max_seq": int(config["max_position_embeddings"]),
            "vocab_size": int(config["vocab_size"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models.phi4flash import (phi4flash_config, phi4flash_init,
                                          phi4flash_logical_axes,
                                          phi4flash_loss)

    cfg = phi4flash_config(config["program"]["preset"],
                           **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: phi4flash_init(key, cfg),
        loss=lambda params, batch: phi4flash_loss(params, batch, cfg),
        logical_axes=lambda: phi4flash_logical_axes(cfg))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree: the head
    counts (the projections are folded), the window and the norms'
    epsilon.  The layers' kinds follow from the tree's stacks."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": s["n_kv_head"],
            "window": s["window"], "eps": s["ln_eps"]}


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """The near-tie tolerance a served answer is held to: 0.6.

    Not ``correct.logit_tie_tol(n_layer)`` (0.05 for 32 layers): that
    one was read off GPT-2's dense blocks, whose engine leaves gaps
    under 0.04.  This model in bf16 rounds as Jamba2-3B does
    (``families/jamba.py``: the bf16 residual stream through 64
    sublayers, MLPs of width 10,240), and its tied head over a hidden of
    2,560 gives logits of std 1.01 at random initialisation.

    The two readings (PERF.md section 4; my chip runs, PR 52), the
    engine at the published widths, bf16 weights, float32 state,
    answers of 768 tokens after prompts of 4,076 tokens, cold and after
    a prefix hit of 254 blocks.  The engine's largest gap over the 46
    checked answers of the cell's own 23 runs, 23 seeds: 0.115 to
    0.237, with 649 to 693 of 768 tokens the reference's own argmax.
    One answer of 768 tokens after a 3,000-token prompt held to the
    reference computed with its weights rounded to fp8 (e4m3, the
    nearest precision below the bf16 the configuration states): 5.99,
    1 of 768 tokens its argmax: not correct (the same answer against
    the reference as it is: 0.142, 686 of 768).  0.6 stands at 2.5
    times the first and a tenth of the second, with the more room above
    the engine's reading, since fresh seeds read higher.  What would
    fail it beside lower precision, on that same answer: the reference
    without the ``lam o2`` term reads 4.14 (43 of 768), with each Gated
    Memory Unit fed the position before's memory 1.72 (270 of 768).
    Each was read through the harness's own comparison
    (``correct.reference_generated_logits`` at the cell's ``max_seq``,
    then ``correct.check_greedy`` at this tolerance: ``ok`` false).
    What it cannot see, on the chip: the SSM state kept in bf16 (as
    for Jamba, the projections' rounding is thirty times the state's).
    Only ``tests/test_phi4flash.py
    test_a_wrong_model_fails_the_tolerance`` holds that, and the
    others, on the CPU in float32, to 1e-4."""
    return 0.6


def _mlp_and_norms(config: Dict[str, Any]) -> int:
    """The SwiGLU MLP (``W_1`` is gate and up, fused) and the layer's
    two LayerNorms with their biases: 78,653,440."""
    d = int(config["hidden_size"])
    return 3 * d * int(config["intermediate_size"]) + 4 * d


def mamba_mixer_params(config: Dict[str, Any]) -> int:
    """One Mamba mixer: in_proj, convolution and bias, x_proj, dt_proj
    and bias, A_log, D, out_proj: 41,241,600."""
    s = sizes(config)
    d, di = s["d_model"], s["expand"] * s["d_model"]
    N, K, R = s["d_state"], s["d_conv"], s["dt_rank"]
    return (d * 2 * di + K * di + di + di * (R + 2 * N) + R * di + di
            + di * N + di + di * d)


def _lambdas_and_norm(config: Dict[str, Any]) -> int:
    s = sizes(config)
    return 6 * (s["d_model"] // s["n_head"])


def self_attention_params(config: Dict[str, Any]) -> int:
    """A window layer's or the full layer's attention: ``W_qkv`` and
    ``W_o`` with biases, four lambda vectors, the pair's norm:
    19,668,864."""
    s = sizes(config)
    d, hd = s["d_model"], s["d_model"] // s["n_head"]
    qkv = (s["n_head"] + 2 * s["n_kv_head"]) * hd
    return d * qkv + qkv + d * d + d + _lambdas_and_norm(config)


def cross_attention_params(config: Dict[str, Any]) -> int:
    """A cross layer's: ``W_q`` and ``W_o`` with biases, lambdas, norm;
    no ``W_k``, ``W_v`` at all: 13,112,704."""
    d = sizes(config)["d_model"]
    return 2 * (d * d + d) + _lambdas_and_norm(config)


def gmu_params(config: Dict[str, Any]) -> int:
    """A Gated Memory Unit's two products: 26,214,400."""
    s = sizes(config)
    return 2 * s["d_model"] * s["expand"] * s["d_model"]


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    """Layers by kind: 9 Mamba, 8 window, 1 full, 7 GMU, 7 cross."""
    L = sizes(config)["n_layer"]
    half = L // 2
    return {"mamba": half // 2 + 1, "window": half // 2, "full": 1,
            "gmu": (L - half - 2) // 2, "cross": (L - half - 2) // 2}


def param_count(config: Dict[str, Any]) -> int:
    """The tied embedding once, the final LayerNorm, and each layer's
    mixer, MLP and two norms: 3,852,562,944."""
    s, n = sizes(config), layer_counts(config)
    return (s["vocab_size"] * s["d_model"] + 2 * s["d_model"]
            + s["n_layer"] * _mlp_and_norms(config)
            + n["mamba"] * mamba_mixer_params(config)
            + (n["window"] + n["full"]) * self_attention_params(config)
            + n["gmu"] * gmu_params(config)
            + n["cross"] * cross_attention_params(config))


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per parameter (the tied embedding counted once), the
    attention's two products at twice a plain head's score work (a
    pair-head is 2 hd wide for each sub-head): a causal triangle on the
    full and cross layers, a band on the window layers; and the scan's
    9 operations per state element, forward and backward.  No cell
    trains this family."""
    s, n = sizes(config), layer_counts(config)
    scan = 3.0 * 9 * s["d_state"] * s["expand"] * s["d_model"]
    attn = 6.0 * 2 * s["d_model"] * (
        (n["full"] + n["cross"]) * seq
        + n["window"] * 2 * min(seq, s["window"]))
    return 6.0 * param_count(config) + attn + n["mamba"] * scan


def _row_bytes(config: Dict[str, Any], itemsize: int) -> int:
    """K and V of one token of one layer: 20 x 64 x 2 x 2 B = 5,120."""
    s = sizes(config)
    return 2 * s["n_kv_head"] * (s["d_model"] // s["n_head"]) * itemsize


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """What one token weighs in the POOL: K and V of the ONE full layer,
    5,120 B, where sixteen attention layers with K/V of their own would
    hold 81,920."""
    return _row_bytes(config, itemsize)


def window_bytes_per_slot(config: Dict[str, Any], itemsize: int = 2) -> int:
    """What one slot's rings weigh: ``sliding_window`` rows of K and V a
    window layer, 8 x 512 x 5,120 = 20,971,520 B, whatever the
    context."""
    s = sizes(config)
    return layer_counts(config)["window"] * s["window"] \
        * _row_bytes(config, itemsize)


def state_bytes_per_slot(config: Dict[str, Any], state_itemsize: int = 4,
                         itemsize: int = 2) -> int:
    """One sequence's recurrent state through every Mamba layer: the
    SSM state (d_inner x d_state, float32) and the convolution's window
    (d_conv - 1 inputs, bf16): 9 x 358,400 = 3,225,600 B."""
    s = sizes(config)
    di = s["expand"] * s["d_model"]
    return layer_counts(config)["mamba"] * (
        di * s["d_state"] * state_itemsize
        + (s["d_conv"] - 1) * di * itemsize)


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The K/V POOL: one layer (``n_layer`` 1 of the model's 32),
    ``num_key_value_heads`` K/V heads of ``head_dim`` 64."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": s["n_kv_head"],
            "head_dim": s["d_model"] // s["n_head"], "n_layer": 1,
            "d_model": s["d_model"]}


def pool_readers(config: Dict[str, Any]) -> int:
    """Layers that read the pool in one decode step: the full layer and
    every cross layer, 8."""
    n = layer_counts(config)
    return n["full"] + n["cross"]


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """A LOWER bound of the HBM bytes one decode step needs: every
    weight once (the tied embedding is read whole for the logits) and
    the pool's K and V of each position attended once for each of the
    eight layers that read them.  The Mamba state and the rings depend
    on the rows that decode, which this signature lacks: left out
    (`ssm_decode_bytes` counts the first)."""
    return param_count(config) * itemsize + pool_readers(config) \
        * kv_bytes_per_token(config, itemsize) * positions_attended


def ssm_decode_bytes(config: Dict[str, Any], rows: float,
                     itemsize: int = 2) -> float:
    """HBM bytes the Mamba mixers of one decode step need: their
    weights once, and each decoding row's state read and written (as
    ``families/jamba.py``'s)."""
    return layer_counts(config)["mamba"] * mamba_mixer_params(config) \
        * itemsize + rows * 2 * state_bytes_per_slot(config,
                                                     itemsize=itemsize)


def shared_kv_decode_bytes(config: Dict[str, Any], contexts,
                           itemsize: int = 2) -> float:
    """HBM bytes the readers of the shared pool need in one decode step,
    from the published sizes alone, whatever implements them: the
    attention weights of the full layer and of the seven cross layers
    once, and for each row its K and V, ``context`` positions, once for
    each of the eight layers that attend them (eight walks of one pool:
    a walk that served several layers' queries would read less, and
    read over 100%).  `contexts`: the rows' context lengths."""
    n = layer_counts(config)
    weights = n["full"] * self_attention_params(config) \
        + n["cross"] * cross_attention_params(config)
    return weights * itemsize + pool_readers(config) \
        * _row_bytes(config, itemsize) * sum(contexts)


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's decode step and
    paged prefill (a prefill that leaves a snapshot: `state`)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.phi4flash_decode import (phi4flash_decode_step,
                                                 phi4flash_init_paged_cache,
                                                 phi4flash_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = phi4flash_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k,
                state):
        logits, cache = phi4flash_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot, state=state)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: phi4flash_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key, i32(3)))]
