"""The decoder families a serving engine can be built over: one row each.

A family is `models/<family>.py` (config, init, forward), its decode
programs in `models/<family>_decode.py`, and a row of `FAMILIES` below.
The decode file is a BLOCK and the bindings of a shared decoder's
programs to it, for a family whose cache is plain K/V (`kv_decode.Block`
over kv_decode.py: gpt2, llama) or K/V beside a matrix state a head
(`delta_decode.Block` over delta_decode.py: solar_open2, olmo_hybrid);
or the family's own five programs (jamba, kimi_k2, laguna, phi4flash,
glm_dsa).  A family's two files import each other and the shared
modules, never another family's files (tests/test_engine_seam.py; the
one edge left is glm_dsa's on kimi_k2, until latent attention has a
module of its own).  What two families call lives in a module named
for what it is:

  layers.py            norms, embedding and head, gated MLP, rotary
                       pairs and YaRN, the forward's cross-entropy
  mamba.py             the Mamba mixer and the convolutions' inputs
  banded_attention.py  grouped-query attention over folded K/V: the
                       whole score matrix, a prefill's banded walk, a
                       window layer's ring, a decode column over the
                       paged pool (and the one choice of its kernel)
  experts.py           the expert layer and its counters
  decode_common.py     the cache's format and operations, per-slot
                       state by layer and by slot, sampling, `generator`

The serving layer (serve/llm.py, serve/engine.py) asks `family(name)`
for the programs and `cache_kind(name)` for what the cache holds, and
names no family itself.

A row's loader imports its modules when it is called, not when this
module is: building a GPT-2 engine never imports the Jamba decoder.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

#: what a family's cache holds.  KV: a slot's past is its K/V rows,
#: which every engine feature can move (rewind by position, spill and
#: restore by block, hand off).  RECURRENT: some layers keep one state
#: per sequence beside the K/V (models/jamba_decode.py: a vector a
#: channel; models/delta_decode.py, for solar_open2 and olmo_hybrid:
#: a matrix a head), and the paged prefill takes one more argument,
#: `state` (decode_common.py: where the slot's state starts and which
#: snapshot it leaves); what cannot carry that state yet is refused
#: when the engine's options are checked.
#: LATENT: positional as KV is (a slot's past is its rows, a prefix is
#: its blocks), but a row is one latent a token, not K and V per head
#: (models/kimi_k2_decode.py; models/glm_dsa_decode.py keeps a third
#: tensor a token beside them, the key a learned indexer scores the
#: position by): what moves K/V rows of one shape, rewinds through a
#: verify program, or splits a heads axis is refused for it.
#: WINDOWED: some layers attend a bounded window and keep, per slot, a
#: ring of their last K/V rows beside the pool of the layers that
#: attend everything (models/laguna_decode.py, over
#: models/banded_attention.py): per-slot state as a
#: recurrent layer's is, carried by the same `state` argument and the
#: same snapshots, and refused where that is.
#: RECURRENT_WINDOWED: both kinds of per-slot state in one slot, a
#: recurrent state a Mamba layer and a ring a window layer, beside a
#: pool that ONE layer writes and several read
#: (models/phi4flash_decode.py).  Every refusal of the two kinds holds
#: for it, for both reasons at once.
KV = "kv"
RECURRENT = "kv+recurrent"
LATENT = "latent"
WINDOWED = "kv+window"
RECURRENT_WINDOWED = "kv+recurrent+window"
#: what a cache that is not plain K/V keeps, as a refusal words it
#: (of an engine option: serve/llm.py; of a mesh: decode_common.py)
CACHE_HOLDS = {
    RECURRENT: "one recurrent state per slot beside the K/V pool",
    LATENT: "a latent pool: one latent and one rotary key a token, no K "
            "or V per head",
    WINDOWED: "a ring of each window layer's last K/V rows per slot "
              "beside the full layers' K/V pool",
    RECURRENT_WINDOWED: "one recurrent state and a ring of each window "
                        "layer's last K/V rows per slot beside the K/V "
                        "pool its layers share",
}
#: the kinds that keep state per SLOT beside the pool: their paged
#: prefill takes `state`, and a prefix is reused from a snapshot of it
PER_SLOT_STATE = frozenset((RECURRENT, WINDOWED, RECURRENT_WINDOWED))


@dataclasses.dataclass(frozen=True)
class Family:
    """One family's programs, as the engine calls them.  `verify` is
    None where the family has no spec-decode verify program."""
    name: str
    cache_kind: str
    config: Callable[..., Any]
    init: Callable[..., Any]
    logical_axes: Callable[..., Any]
    generate: Callable[..., Any]
    prefill: Callable[..., Any]
    paged_prefill: Callable[..., Any]
    step: Callable[..., Any]
    verify: Optional[Callable[..., Any]]
    init_cache: Callable[..., Any]
    init_paged_cache: Callable[..., Any]
    #: ``(cfg, t_pad, prefix_len, n_tail) -> (a kernel attended, tile
    #: pairs walked, pairs without the diagonal)`` of a paged prefill,
    #: for the host's count; None where the family has one path
    prefill_attention: Optional[Callable[..., Any]] = None


def _gpt2() -> Dict[str, Any]:
    from ray_tpu.models import gpt2_decode as m
    from ray_tpu.models.gpt2 import (gpt2_config, gpt2_init,
                                     gpt2_logical_axes)

    return dict(
        config=gpt2_config, init=gpt2_init,
        logical_axes=gpt2_logical_axes, generate=m.generate,
        prefill=m.prefill, paged_prefill=m.paged_prefill,
        step=m.decode_step, verify=m.verify_step,
        init_cache=m.init_cache, init_paged_cache=m.init_paged_cache)


def _llama() -> Dict[str, Any]:
    from ray_tpu.models import llama_decode as m
    from ray_tpu.models.llama import (llama_config, llama_init,
                                      llama_logical_axes)

    return dict(
        config=llama_config, init=llama_init,
        logical_axes=llama_logical_axes, generate=m.llama_generate,
        prefill=m.llama_prefill, paged_prefill=m.llama_paged_prefill,
        step=m.llama_decode_step, verify=m.llama_verify_step,
        init_cache=m.llama_init_cache,
        init_paged_cache=m.llama_init_paged_cache)


def _jamba() -> Dict[str, Any]:
    from ray_tpu.models import jamba_decode as m
    from ray_tpu.models.jamba import (jamba_config, jamba_init,
                                      jamba_logical_axes)

    return dict(
        config=jamba_config, init=jamba_init,
        logical_axes=jamba_logical_axes, generate=m.jamba_generate,
        prefill=m.jamba_prefill, paged_prefill=m.jamba_paged_prefill,
        step=m.jamba_decode_step, verify=None,
        init_cache=m.jamba_init_cache,
        init_paged_cache=m.jamba_init_paged_cache)


def _kimi_k2() -> Dict[str, Any]:
    from ray_tpu.models import kimi_k2_decode as m
    from ray_tpu.models.kimi_k2 import (kimi_k2_config, kimi_k2_init,
                                        kimi_k2_logical_axes)

    return dict(
        config=kimi_k2_config, init=kimi_k2_init,
        logical_axes=kimi_k2_logical_axes, generate=m.kimi_k2_generate,
        prefill=m.kimi_k2_prefill, paged_prefill=m.kimi_k2_paged_prefill,
        step=m.kimi_k2_decode_step, verify=None,
        init_cache=m.kimi_k2_init_cache,
        init_paged_cache=m.kimi_k2_init_paged_cache,
        prefill_attention=m.kimi_k2_prefill_attention)


def _laguna() -> Dict[str, Any]:
    from ray_tpu.models import laguna_decode as m
    from ray_tpu.models.laguna import (laguna_config, laguna_init,
                                       laguna_logical_axes)

    return dict(
        config=laguna_config, init=laguna_init,
        logical_axes=laguna_logical_axes, generate=m.laguna_generate,
        prefill=m.laguna_prefill, paged_prefill=m.laguna_paged_prefill,
        step=m.laguna_decode_step, verify=None,
        init_cache=m.laguna_init_cache,
        init_paged_cache=m.laguna_init_paged_cache,
        prefill_attention=m.laguna_prefill_attention)


def _solar_open2() -> Dict[str, Any]:
    from ray_tpu.models import solar_open2_decode as m
    from ray_tpu.models.solar_open2 import (solar_open2_config,
                                            solar_open2_init,
                                            solar_open2_logical_axes)

    return dict(
        config=solar_open2_config, init=solar_open2_init,
        logical_axes=solar_open2_logical_axes,
        generate=m.solar_open2_generate, prefill=m.solar_open2_prefill,
        paged_prefill=m.solar_open2_paged_prefill,
        step=m.solar_open2_decode_step, verify=None,
        init_cache=m.solar_open2_init_cache,
        init_paged_cache=m.solar_open2_init_paged_cache,
        prefill_attention=m.solar_open2_prefill_attention)


def _phi4flash() -> Dict[str, Any]:
    from ray_tpu.models import phi4flash_decode as m
    from ray_tpu.models.phi4flash import (phi4flash_config, phi4flash_init,
                                          phi4flash_logical_axes)

    return dict(
        config=phi4flash_config, init=phi4flash_init,
        logical_axes=phi4flash_logical_axes,
        generate=m.phi4flash_generate, prefill=m.phi4flash_prefill,
        paged_prefill=m.phi4flash_paged_prefill,
        step=m.phi4flash_decode_step, verify=None,
        init_cache=m.phi4flash_init_cache,
        init_paged_cache=m.phi4flash_init_paged_cache,
        prefill_attention=m.phi4flash_prefill_attention)


def _olmo_hybrid() -> Dict[str, Any]:
    from ray_tpu.models import olmo_hybrid_decode as m
    from ray_tpu.models.olmo_hybrid import (olmo_hybrid_config,
                                            olmo_hybrid_init,
                                            olmo_hybrid_logical_axes)

    return dict(
        config=olmo_hybrid_config, init=olmo_hybrid_init,
        logical_axes=olmo_hybrid_logical_axes,
        generate=m.olmo_hybrid_generate, prefill=m.olmo_hybrid_prefill,
        paged_prefill=m.olmo_hybrid_paged_prefill,
        step=m.olmo_hybrid_decode_step, verify=None,
        init_cache=m.olmo_hybrid_init_cache,
        init_paged_cache=m.olmo_hybrid_init_paged_cache,
        prefill_attention=m.olmo_hybrid_prefill_attention)


def _glm_dsa() -> Dict[str, Any]:
    from ray_tpu.models import glm_dsa_decode as m
    from ray_tpu.models.glm_dsa import (glm_dsa_config, glm_dsa_init,
                                        glm_dsa_logical_axes)

    return dict(
        config=glm_dsa_config, init=glm_dsa_init,
        logical_axes=glm_dsa_logical_axes, generate=m.glm_dsa_generate,
        prefill=m.glm_dsa_prefill, paged_prefill=m.glm_dsa_paged_prefill,
        step=m.glm_dsa_decode_step, verify=None,
        init_cache=m.glm_dsa_init_cache,
        init_paged_cache=m.glm_dsa_init_paged_cache)


#: family -> (what its cache holds, loader of its programs)
FAMILIES: Dict[str, Tuple[str, Callable[[], Dict[str, Any]]]] = {
    "gpt2": (KV, _gpt2), "llama": (KV, _llama),
    "jamba": (RECURRENT, _jamba), "kimi_k2": (LATENT, _kimi_k2),
    "laguna": (WINDOWED, _laguna),
    "solar_open2": (RECURRENT, _solar_open2),
    "phi4flash": (RECURRENT_WINDOWED, _phi4flash),
    "olmo_hybrid": (RECURRENT, _olmo_hybrid),
    "glm_dsa": (LATENT, _glm_dsa)}


def cache_kind(name: str) -> Optional[str]:
    """What the named family's cache holds, without loading it; None
    for a name that is no family."""
    return FAMILIES.get(name, (None,))[0]


def family(name: str) -> Family:
    """The named family's adapter (its modules are imported now)."""
    kind, load = FAMILIES[name]
    return Family(name=name, cache_kind=kind, **load())
