"""Autoregressive decoding for the Olmo-Hybrid family: K/V for the one
full layer in four, a matrix a head for the three linear layers, in one
cache.

The programs are `delta_decode.py`'s, the decoder of every family that
keeps a matrix a head beside K/V (the cache in both layouts, the
`state` argument of the paged prefill, the snapshot pool: read them
there).  This module is the Olmo-Hybrid block they run over, and their
binding under the family's public names.  The K/V tensors hold the full
layers only, ``kv_width`` = n_kv_head * head_dim lanes a row: with as
many K/V heads as query heads that is the whole model width, 3.75 times
a grouped-query layer's row, and the POOL, not the state, bounds the
batch.  Beside them:

  conv : (n_linear, d_conv - 1, B, conv_width)   the three convolutions'
         window, q, k and v side by side, compute dtype
  ssm  : (n_linear, B, heads, key dim, value dim)   the delta rule's
         state, float32: 2.21 MB a layer a slot at the published sizes

No layer has experts: the blocks hand the decoder no stats and the
cache keeps no counters.
"""

from __future__ import annotations

from functools import partial

from ray_tpu.models import delta_decode
from ray_tpu.models.decode_common import generator
from ray_tpu.models.olmo_hybrid import (FULL, embed, full_block,
                                        linear_block, lm_logits,
                                        walk_layers, zero_recurrent)

__all__ = ["olmo_hybrid_init_cache", "olmo_hybrid_init_paged_cache",
           "olmo_hybrid_prefill", "olmo_hybrid_paged_prefill",
           "olmo_hybrid_decode_step", "olmo_hybrid_generate",
           "olmo_hybrid_prefill_attention"]


def _full(x, p, cfg, attend, valid):
    return full_block(x, p, cfg, attend), None


def _linear(x, p, cfg, window, state, real, capture=None, layer=None):
    x, after, snap = linear_block(x, p, cfg, window, state, real, capture,
                                  layer)
    return x, None, after, snap


def _walk(cfg, params, x, layer):
    return walk_layers(cfg, params, x,
                       lambda x, p, kind, j: layer(x, p, kind, j)[0]), None


BLOCK = delta_decode.Block(
    family="olmo_hybrid", attn=FULL, zero_recurrent=zero_recurrent,
    embed=embed, attn_block=_full, rule_block=_linear, walk_layers=_walk,
    lm_logits=lm_logits)

# delta_decode's programs over the block (each documented there)
olmo_hybrid_init_cache = partial(delta_decode.init_cache, BLOCK)
olmo_hybrid_init_paged_cache = partial(delta_decode.init_paged_cache, BLOCK)
olmo_hybrid_prefill = partial(delta_decode.prefill, BLOCK)
olmo_hybrid_paged_prefill = partial(delta_decode.paged_prefill, BLOCK)
olmo_hybrid_decode_step = partial(delta_decode.decode_step, BLOCK)
olmo_hybrid_prefill_attention = partial(delta_decode.prefill_attention,
                                        BLOCK)
#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle.  kv_layout="paged" re-lays the full layers' K/V into
#: blocks after the prefill (the recurrent state is per row in both
#: layouts)
olmo_hybrid_generate = generator(olmo_hybrid_prefill,
                                 olmo_hybrid_decode_step)
