"""Family ``gpt2``: what the benchmark has to know of one model family,
in one file.  A configuration names its family under
``program.family``; the drivers, the readers and the rehearsal tools
find this file by that name (``cells.load_family``) and ask it for
everything that depends on the architecture.  A later PR adds a family
(a mixture of experts, another attention) by adding
``families/<name>.py`` with these names, ``reference/<REFERENCE>.py``
and ``rehearsal/<name>.json`` beside it, and edits no file that is
there (``tests/benchmark/data/second_family`` is one, whole):

``REFERENCE``
    name of the plain reference, ``benchmark/reference/<name>.py``, with
    ``logits(params, tokens, vocab_size=, **kw)`` and ``loss(params,
    tokens, vocab_size=, **kw)``; ``kw`` is ``reference_kwargs`` below.
``sizes(config)``
    the published sizes, under whatever keys the source's
    ``config.json`` spells them with, as the program's configuration
    overrides.  The harness reads ``d_model``, ``n_head`` and
    ``vocab_size`` out of it and hands the rest to ``program``.
``program(config, overrides)``
    the program's own model: ``cfg``, ``init(key)``, ``loss(params,
    batch)``, ``logical_axes()``, imported from ``ray_tpu.models`` here
    and nowhere else in the benchmark.
``param_count``, ``train_flops_per_token``, ``decode_step_bytes``,
``kv_bytes_per_token``, ``attention_shape``
    the yardstick's arithmetic from the published sizes alone: nothing
    here asks the program or the compiler what it did.  Recomputed
    operations (remat) are not counted.  ``attention_shape`` gives
    ``n_head``, ``head_dim`` (the configuration's own where it states
    one, whatever ``d_model / n_head`` is), ``n_layer``, ``d_model``
    and, where K and V have fewer heads than Q, ``n_kv_head``: the K/V
    pool's shape follows from it.
``aot_serve_programs``
    for ``benchmark/aot_fit.py`` only: the engine's decode and prefill
    programs over abstract arguments.

A family may also state, and this one states neither:

``reference_kwargs(config)``
    what its reference needs that the parameter tree does not show (a
    rotary base, a norm's epsilon, the experts a token takes), read
    from the configuration.  GPT-2's one such number, the LayerNorm
    epsilon, is the reference's default.
``logit_tie_tol(config)``
    the near-tie tolerance its served answers are held to
    (``serving.tie_tol``), with its reason and the readings behind it
    written beside it.  Without it ``correct.logit_tie_tol(n_layer)``
    stands, which was derived for dense pre-norm blocks in bf16: 0.03
    for this family's twelve layers, 0.06 for the XL's 48.
"""

from __future__ import annotations

import types
from typing import Any, Dict

REFERENCE = "gpt2"


def sizes(config: Dict[str, Any]) -> Dict[str, Any]:
    """The published sizes (Hugging Face ``config.json`` keys) as the
    keyword overrides the program's ``gpt2_config`` takes."""
    d = int(config["n_embd"])
    return {"n_layer": int(config["n_layer"]),
            "n_head": int(config["n_head"]), "d_model": d,
            "d_ff": int(config["n_inner"] or 4 * d),
            "max_seq": int(config["n_positions"]),
            "vocab_size": int(config["vocab_size"])}


def program(config: Dict[str, Any], overrides: Dict[str, Any]):
    from ray_tpu.models import (gpt2_config, gpt2_init, gpt2_logical_axes,
                                gpt2_loss)

    cfg = gpt2_config(config["program"]["preset"],
                      **{**sizes(config), **overrides})
    return types.SimpleNamespace(
        cfg=cfg, init=lambda key: gpt2_init(key, cfg),
        loss=lambda params, batch: gpt2_loss(params, batch, cfg),
        logical_axes=lambda: gpt2_logical_axes(cfg))


def param_count(config: Dict[str, Any]) -> int:
    """Parameters of a GPT-2 (tied embedding counted once, positions
    included), from the published sizes."""
    d, L = int(config["n_embd"]), int(config["n_layer"])
    f = int(config["n_inner"] or 4 * d)
    per_layer = (3 * d * d + 3 * d) + (d * d + d) \
        + (2 * d * f + d + f) + 4 * d          # qkv, out, mlp, 2 LN
    return (int(config["vocab_size"]) * d
            + int(config["n_positions"]) * d + L * per_layer + 2 * d)


def train_flops_per_token(config: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations one trained token requires:
    6 per parameter, plus causal attention's two T x T matmuls per
    layer (QK^T and PV, 2*T*d each forward, half of it under the causal
    mask, three times that with the backward pass): 6*L*T*d."""
    d, L = int(config["n_embd"]), int(config["n_layer"])
    return 6.0 * param_count(config) + 6.0 * L * seq * d


def kv_bytes_per_token(config: Dict[str, Any], itemsize: int = 2) -> int:
    """K and V of one token through every layer (bf16)."""
    return 2 * int(config["n_layer"]) * int(config["n_embd"]) * itemsize


def decode_step_bytes(config: Dict[str, Any], positions_attended: float,
                      itemsize: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once (the learned
    positions are indexed, not read whole), and the K and V of each
    position attended (summed over the active rows)."""
    d = int(config["n_embd"])
    weights = (param_count(config)
               - int(config["n_positions"]) * d) * itemsize
    return weights + kv_bytes_per_token(config, itemsize) \
        * positions_attended


def attention_shape(config: Dict[str, Any]) -> Dict[str, int]:
    """What the flash kernels' FLOPs and bytes follow from."""
    d, h = int(config["n_embd"]), int(config["n_head"])
    return {"n_head": h, "head_dim": d // h,
            "n_layer": int(config["n_layer"]), "d_model": d}


def aot_serve_programs(cfg, slots: int, block_size: int, t_pad: int,
                       place):
    """(``cache_shapes(n_blocks)``, [(name, fn, args after params and
    cache)]): the engine's decode step and one prefill of `t_pad`
    tokens, as ``serve/llm.py`` builds them.  `place` turns a shape and
    dtype into an abstract argument on the described device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.decode_common import (make_vocab_tail_mask,
                                              sample_token)
    from ray_tpu.models.gpt2_decode import (decode_step, init_paged_cache,
                                            paged_prefill)

    tail = make_vocab_tail_mask(cfg)

    def pool_step(p, cache, toks, k):
        logits, cache = decode_step(p, cache, toks, cfg)
        return sample_token(logits, k, 0.0, tail, 0, 1.0), cache

    def prefill(p, cache, toks, row_bt, prefix_len, n_tail, slot, k):
        logits, cache = paged_prefill(p, cache, toks, cfg, row_bt=row_bt,
                                      prefix_len=prefix_len,
                                      n_tail=n_tail, slot=slot)
        return sample_token(logits[None], k, 0.0, tail, 0, 1.0), cache

    def cache_shapes(n_blocks: int):
        return jax.eval_shape(lambda: init_paged_cache(
            cfg, slots, num_blocks=n_blocks, block_size=block_size))

    i32 = lambda *shape: place(shape, jnp.int32)  # noqa: E731
    key = place((2,), jnp.uint32)
    return cache_shapes, [
        ("decode", pool_step, (i32(slots), key)),
        ("prefill", prefill, (i32(1, t_pad), i32(cfg.max_seq // block_size),
                              i32(), i32(), i32(), key))]
