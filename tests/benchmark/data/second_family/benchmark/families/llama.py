"""Family ``llama`` (a test's fixture; see ../../README.txt): what
``benchmark/families/gpt2.py``'s docstring asks of a family file, for
the program's RMSNorm / RoPE / SwiGLU / grouped-query decoder, from the
keys of a published ``config.json``."""

from __future__ import annotations

import types
from typing import Any, Dict

REFERENCE = "llama"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes as the keyword overrides the program's
    ``llama_config`` takes.  The program's head is ``d_model //
    n_head`` wide; a configuration that states another ``head_dim`` is
    not this program's to run."""
    d, h = int(config["hidden_size"]), int(config["num_attention_heads"])
    if int(config.get("head_dim", d // h)) != d // h:
        raise SystemExit("family llama: head_dim is not hidden_size / "
                         "num_attention_heads")
    return {"n_layer": int(config["num_hidden_layers"]), "n_head": h,
            "n_kv_head": int(config["num_key_value_heads"]),
            "d_model": d, "d_ff": int(config["intermediate_size"]),
            "max_seq": int(config["max_position_embeddings"]),
            "vocab_size": int(config["vocab_size"]),
            "rope_theta": float(config["rope_theta"]),
            "rms_eps": float(config["rms_norm_eps"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models import (llama_config, llama_init,
                                llama_logical_axes, llama_loss)

    cfg = llama_config(config["program"]["preset"],
                       **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: llama_init(key, cfg),
        loss=lambda params, batch: llama_loss(params, batch, cfg),
        logical_axes=lambda: llama_logical_axes(cfg))


def reference_kwargs(config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference cannot read off the parameter tree."""
    return {"rope_theta": float(config["rope_theta"]),
            "rms_eps": float(config["rms_norm_eps"])}


def logit_tie_tol(config: Dict[str, Any]) -> float:
    """Stated, not derived: this family is only ever walked on the CPU
    in float32, where rounding puts about 1e-6 on a logit of two layers;
    1e-3 leaves three orders of room.  A family that is measured states
    what its chip runs showed (both readings, as PERF.md asks)."""
    return 1e-3


def _per_layer(config: Dict[str, Any]) -> int:
    s = sizes(config)
    d, hd = s["d_model"], s["d_model"] // s["n_head"]
    attn = 2 * d * s["n_head"] * hd + 2 * d * s["n_kv_head"] * hd
    return attn + 3 * d * s["d_ff"] + 2 * d        # + two RMSNorm scales


def param_count(config: Dict[str, Any]) -> int:
    """Embedding and untied head, the layers, the final norm."""
    s = sizes(config)
    return (2 * s["vocab_size"] * s["d_model"]
            + s["n_layer"] * _per_layer(config) + s["d_model"])


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """6 per parameter the token meets (the embedding is a lookup) plus
    causal attention's two T x T matmuls a layer: 6*L*T*d."""
    s = sizes(config)
    return 6.0 * (param_count(config) - s["vocab_size"] * s["d_model"]) \
        + 6.0 * s["n_layer"] * seq * s["d_model"]


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one token through every layer: K/V heads only."""
    s = sizes(config)
    return 2 * s["n_layer"] * s["n_kv_head"] \
        * (s["d_model"] // s["n_head"]) * itemsize


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """Every weight once (the embedding is indexed, not read whole) and
    the K and V of each position attended."""
    s = sizes(config)
    weights = (param_count(config)
               - s["vocab_size"] * s["d_model"]) * itemsize
    return weights + kv_bytes_per_token(config, itemsize) \
        * positions_attended


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What the flash kernels' FLOPs and bytes follow from (K and V are
    repeated to ``n_head`` before the kernel), and ``n_kv_head``: the
    heads the K/V pool holds."""
    s = sizes(config)
    return {"n_head": s["n_head"], "n_kv_head": s["n_kv_head"],
            "head_dim": s["d_model"] // s["n_head"],
            "n_layer": s["n_layer"], "d_model": s["d_model"]}


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """As ``families/gpt2.py``'s, over the program's llama decode step
    and paged prefill."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.llama_decode import (llama_decode_step,
                                             llama_init_paged_cache,
                                             llama_paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = llama_decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k):
        logits, cache = llama_paged_prefill(
            p, cache, toks, cfg, row_bt=row_bt, prefix_len=prefix_len,
            n_tail=n_tail, slot=slot)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: llama_init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key))]
