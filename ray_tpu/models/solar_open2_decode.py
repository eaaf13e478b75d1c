"""Autoregressive decoding for the Solar-Open2 family: K/V for the one
softmax layer in four, a matrix a head for the three KDA layers, in one
cache.

The programs are `delta_decode.py`'s, the decoder of every family that
keeps a matrix a head beside K/V (the cache in both layouts, the
`state` argument of the paged prefill, the snapshot pool: read them
there).  This module is the Solar-Open2 block they run over, and their
binding under the family's public names:

  conv : (n_kda, d_conv - 1, B, 3 * kda_width)   the three convolutions'
         window, compute dtype
  ssm  : (n_kda, B, heads, head_dim, head_dim)   the delta rule's state,
         float32: 4.19 MB a layer a slot at the published sizes

Every layer ends in an expert layer: ``cache["experts"]`` holds what
their routing did in the LAST program (decode_common.EXPERT_COUNTERS),
and a pad or an idle row is routed to no expert (`gqa_block`'s and
`kda_block`'s `valid`).
"""

from __future__ import annotations

from functools import partial

from ray_tpu.models import delta_decode
from ray_tpu.models.decode_common import generator
from ray_tpu.models.solar_open2 import (GQA, embed, gqa_block, kda_block,
                                        lm_logits, walk_layers,
                                        zero_recurrent)

__all__ = ["solar_open2_init_cache", "solar_open2_init_paged_cache",
           "solar_open2_prefill", "solar_open2_paged_prefill",
           "solar_open2_decode_step", "solar_open2_generate",
           "solar_open2_prefill_attention"]

BLOCK = delta_decode.Block(
    family="solar_open2", attn=GQA, zero_recurrent=zero_recurrent,
    embed=embed, attn_block=gqa_block, rule_block=kda_block,
    walk_layers=walk_layers, lm_logits=lm_logits)

# delta_decode's programs over the block (each documented there)
solar_open2_init_cache = partial(delta_decode.init_cache, BLOCK)
solar_open2_init_paged_cache = partial(delta_decode.init_paged_cache, BLOCK)
solar_open2_prefill = partial(delta_decode.prefill, BLOCK)
solar_open2_paged_prefill = partial(delta_decode.paged_prefill, BLOCK)
solar_open2_decode_step = partial(delta_decode.decode_step, BLOCK)
solar_open2_prefill_attention = partial(delta_decode.prefill_attention,
                                        BLOCK)
#: generation via the shared loop (decode_common.generate_with): one
#: dense prefill, then the decode step scanned; the serve engine's
#: parity oracle.  kv_layout="paged" re-lays the GQA layers' K/V into
#: blocks after the prefill (the recurrent state is per row in both
#: layouts)
solar_open2_generate = generator(solar_open2_prefill,
                                 solar_open2_decode_step)
