"""Family ``jamba``: what the benchmark has to know of AI21's hybrid of
Mamba and attention layers (``families/gpt2.py``'s docstring lists what
a family file holds), from the keys of the published ``config.json``.

Layer ``i`` of ``num_hidden_layers`` is an attention layer iff
``i % attn_layer_period == attn_layer_offset``, a Mamba layer otherwise;
every layer ends in a SwiGLU MLP (``num_experts`` 1: the
``expert_layer_*`` keys select nothing).  Two kinds of cache follow:
K/V for the attention layers alone, and per SEQUENCE, not per token, a
convolution window and an SSM state for each Mamba layer.  The harness
asks ``attention_shape`` for the K/V pool, so it describes the
attention layers; what the recurrent state costs is stated apart
(``state_bytes_per_slot``, ``ssm_decode_bytes``) and read by the
metrics this family brings (``metrics/ssm_*.py``).
"""

from __future__ import annotations

import types
from typing import Any, Dict

REFERENCE = "jamba"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as the keyword overrides the program's
    ``jamba_config`` takes.  ``max_seq`` is the context a replica is
    given: no weight depends on it (the model has no position table),
    so a serving cell sets it in its traffic file as a server's
    ``max_model_len`` is set, below the 262,144 the source allows."""
    if int(config["num_experts"]) != 1:
        raise SystemExit("family jamba: the program has no expert layers "
                         "(num_experts must be 1)")
    if not config["mamba_conv_bias"] or config["mamba_proj_bias"]:
        raise SystemExit("family jamba: the program's mixer has a "
                         "convolution bias and no projection bias")
    return {"n_layer": int(config["num_hidden_layers"]),
            "n_head": int(config["num_attention_heads"]),
            "n_kv_head": int(config["num_key_value_heads"]),
            "d_model": int(config["hidden_size"]),
            "d_ff": int(config["intermediate_size"]),
            "attn_period": int(config["attn_layer_period"]),
            "attn_offset": int(config["attn_layer_offset"]),
            "d_state": int(config["mamba_d_state"]),
            "d_conv": int(config["mamba_d_conv"]),
            "dt_rank": int(config["mamba_dt_rank"]),
            "expand": int(config["mamba_expand"]),
            "max_seq": int(config["max_position_embeddings"]),
            "vocab_size": int(config["vocab_size"]),
            "rms_eps": float(config["rms_norm_eps"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models.jamba import (jamba_config, jamba_init,
                                      jamba_logical_axes, jamba_loss)

    cfg = jamba_config(config["program"]["preset"],
                       **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: jamba_init(key, cfg),
        loss=lambda params, batch: jamba_loss(params, batch, cfg),
        logical_axes=lambda: jamba_logical_axes(cfg))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree: the
    norm's epsilon and which layers are attention."""
    return {"eps": float(config["rms_norm_eps"]),
            "attn_period": int(config["attn_layer_period"]),
            "attn_offset": int(config["attn_layer_offset"])}


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """The near-tie tolerance a served answer is held to: 0.5.

    Not ``correct.logit_tie_tol(n_layer)`` (0.046 here): that one was
    read off GPT-2, whose engine leaves gaps under 0.04.  This model in
    bf16 rounds harder, and it is rounding, not a fault: against the
    float32 reference the bf16 program's logits (std 1.01 at random
    initialisation) carry an rms error of 0.050, the same program in
    float32 at ``highest`` precision 4e-6 (max 2.9e-5).  Most of it is
    the dense part's, the MLP of width 8,192 and the bf16 residual
    stream through 56 sublayers; the Mamba mixers' own bf16 is about a
    tenth of the squared error (read at width 512 on the CPU).

    The two readings (PERF.md section 4; my chip run, PR 28): the
    engine at the published widths, bf16 weights, float32 state, over
    the checked answers of 512 tokens each that its runs made: largest
    gap 0.147 to 0.228, with 435 to 470 of 512 tokens the reference's
    own argmax; weights rounded to fp8 through the same program: 5.2 to
    6.0, 350 of 352 tokens wrong.  0.5 stands at twice the first and a
    tenth of the second.  What it cannot see: the SSM state kept in
    bf16 instead of float32 reads 0.089 and 0.204 where float32 reads
    0.178 and 0.166 on the same tokens, since the projections' rounding
    is thirty times the state's; tests/test_jamba.py holds the state's
    precision, on the CPU in float32, where a bf16 state misses by two
    hundred times the float32 program's error."""
    return 0.5


def _mlp_and_norms(config: Dict[str, Any]) -> int:
    d = int(config["hidden_size"])
    return 3 * d * int(config["intermediate_size"]) + 2 * d


def mamba_mixer_params(config: Dict[str, Any]) -> int:
    """One Mamba mixer: in_proj, convolution and bias, x_proj, dt_proj
    and bias, A_log, D, out_proj, and the three norms of dt, B and C."""
    s = sizes(config)
    d, di = s["d_model"], s["expand"] * s["d_model"]
    N, K, R = s["d_state"], s["d_conv"], s["dt_rank"]
    return (d * 2 * di + K * di + di + di * (R + 2 * N) + R * di + di
            + di * N + di + di * d + R + 2 * N)


def _attention_params(config: Dict[str, Any]) -> int:
    s = sizes(config)
    d, hd = s["d_model"], s["d_model"] // s["n_head"]
    return 2 * d * s["n_head"] * hd + 2 * d * s["n_kv_head"] * hd


def layer_counts(config: Dict[str, Any]) -> Dict[str, int]:
    s = sizes(config)
    n_attn = sum(i % s["attn_period"] == s["attn_offset"]
                 for i in range(s["n_layer"]))
    return {"attention": n_attn, "mamba": s["n_layer"] - n_attn}


def param_count(config: Dict[str, Any]) -> int:
    """The tied embedding once, the final norm, and each layer's mixer,
    MLP and two norms: 3,029,337,472 for Jamba2-3B."""
    s, n = sizes(config), layer_counts(config)
    return (s["vocab_size"] * s["d_model"] + s["d_model"]
            + n["mamba"] * (mamba_mixer_params(config)
                            + _mlp_and_norms(config))
            + n["attention"] * (_attention_params(config)
                                + _mlp_and_norms(config)))


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per parameter (the tied embedding is a lookup on the way in and
    a matmul on the way out: counted once), causal attention's two
    T x T matmuls in the attention layers (6*T*d each), and the scan's
    9 operations per state element per token, forward and backward
    (3x): exp-multiply-add into the state and multiply-add out of it."""
    s, n = sizes(config), layer_counts(config)
    scan = 3.0 * 9 * s["d_state"] * s["expand"] * s["d_model"]
    return 6.0 * param_count(config) \
        + 6.0 * n["attention"] * seq * s["d_model"] + n["mamba"] * scan


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one token through the ATTENTION layers, K/V heads
    only: 1,024 B for Jamba2-3B."""
    s = sizes(config)
    return 2 * layer_counts(config)["attention"] * s["n_kv_head"] \
        * (s["d_model"] // s["n_head"]) * itemsize


def state_bytes_per_slot(config: Dict[str, Any], state_itemsize: int = 4,
                         itemsize: int = 2) -> int:
    """One sequence's recurrent state through every Mamba layer: the
    SSM state (d_inner x d_state, float32) and the convolution's window
    (d_conv - 1 inputs, bf16): 9,318,400 B for Jamba2-3B."""
    s = sizes(config)
    di = s["expand"] * s["d_model"]
    return layer_counts(config)["mamba"] * (
        di * s["d_state"] * state_itemsize
        + (s["d_conv"] - 1) * di * itemsize)


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once (the tied
    embedding is read whole for the logits) and the K and V of each
    position attended.  The recurrent state's bytes depend on the rows
    that decode, which this signature lacks: `ssm_decode_bytes` counts
    them, for ``metrics/ssm_decode_roofline``."""
    return param_count(config) * itemsize \
        + kv_bytes_per_token(config, itemsize) * positions_attended


def ssm_decode_bytes(config: Dict[str, Any], rows: float,
                     itemsize: int = 2) -> float:
    """HBM bytes the Mamba mixers of one decode step need: their
    weights once, and each decoding row's state read and written."""
    return layer_counts(config)["mamba"] * mamba_mixer_params(config) \
        * itemsize + rows * 2 * state_bytes_per_slot(config,
                                                     itemsize=itemsize)


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The ATTENTION layers: what the K/V pool's shape and bytes follow
    from (``n_layer`` is their number, not the model's depth)."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": s["n_kv_head"],
            "head_dim": s["d_model"] // s["n_head"],
            "n_layer": layer_counts(config)["attention"],
            "d_model": s["d_model"]}


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's jamba decode step
    and paged prefill (with its `state` argument, as the engine calls
    it)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.jamba_decode import (jamba_decode_step,
                                             jamba_init_paged_cache,
                                             jamba_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = jamba_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k,
                state):
        logits, cache = jamba_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot, state=state)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: jamba_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key, i32(3)))]
