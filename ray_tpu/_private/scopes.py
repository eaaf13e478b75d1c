"""The names the program gives its own work on the profiler's timeline.

Two kinds, one registry (``PERF.md`` §3 quotes these constants; only
``benchmark/reduce/program.py`` spells the strings again, because it
has to load against a checkout without this module, and a test holds
the two equal):

* **device scopes** -- ``jax.named_scope`` names inside the jitted
  programs.  A scope is metadata only: it lands in every HLO
  instruction's ``op_name`` (``jit(step)/loss_and_grad/jvp(attn)/...``)
  and costs nothing at run time.  A trace event carries the
  instruction's name, not its metadata, so the join is by instruction
  name, checked by result type and opcode: :func:`scope_map_from_hlo`
  reads a compiled program's text once into ``{instruction: {key:
  innermost registered scope}}``
  (``device_stats.ProgramRegistry.scope_map``).
* **host phases** -- ``raytpu.<layer>.<phase>`` spans that
  ``_private/telemetry.Phases`` opens as ``jax.profiler
  .TraceAnnotation``, so they sit on the profiler's own clock beside the
  device events.

This module imports nothing heavy: the runtime's driver never imports
JAX.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

# -- device scopes: models ---------------------------------------------------
EMBED = "embed"              # token + position lookup
ATTN = "attn"                # qkv projection, attention, output projection
MLP = "mlp"
LN = "ln"                    # every layernorm call site
LM_HEAD_CE = "lm_head_ce"    # training: tied logits + cross-entropy
LM_HEAD = "lm_head"          # decoding: final logits
KV_POOL = "kv_pool"          # reads and writes of the K/V cache or pool
SAMPLE = "sample"            # decode_common.sample_token
#: a Mamba mixer's projections, convolution, scan and gate (models/jamba.py)
SSM = "ssm"
#: reads and writes of the recurrent state (convolution window and SSM
#: state per sequence) and of its snapshot pool: ``kv_pool``'s twin
SSM_STATE = "ssm_state"
#: latent attention (models/kimi_k2.py): the down and up projections of
#: queries and of the cached latent, RoPE, and both attention paths
#: (expanded for a prefill, absorbed for a decode step)
MLA = "mla"
#: a learned indexer that picks what latent attention may read
#: (models/glm_dsa.py, ops/dsa.py): its query, key and head-weight
#: projections, the key's LayerNorm and rotary, the index scores, the
#: top-k, and the mask or the gather's indices built from it.  The
#: attention over the selected rows keeps ``mla``; reads and writes of
#: the index keys' pool and the gather of selected latents ``kv_pool``
ATTN_INDEX = "attn_index"
#: a sparse expert layer (models/experts.py): scores and top-k ...
MOE_ROUTER = "moe_router"
#: ... and the held experts' part: which choices are local and their
#: counts, the rows' dispatch into grouped order, the grouped matmuls
#: and the weighted combine onto the tokens' rows; the shared expert
#: and a dense layer's MLP keep ``mlp``
MOE_EXPERTS = "moe_experts"
#: a layer that attends the whole context, and one that attends a
#: bounded window of it (models/laguna.py: the two kinds differ in
#: head count, rotary and reach): each kind's projections, rotary,
#: scores, per-head gate and output projection.  Laguna's rings pass
#: under ``kv_pool``; models/phi4flash_decode.py, whose pool is one
#: layer's that eight read, keeps its rings' writes and slices under
#: ``attn_window``
ATTN_FULL = "attn_full"
ATTN_WINDOW = "attn_window"
#: a linear-attention layer whose state is a matrix a head
#: (models/solar_open2.py, ops/kda.py): its projections, convolutions,
#: gates, the delta rule, the output norm and output projection
ATTN_LINEAR = "attn_linear"
#: reads and writes of such layers' per-slot matrices and convolution
#: windows and of their snapshot pool: ``ssm_state``'s twin
LINEAR_STATE = "linear_state"
#: a layer that keeps no K/V of its own and attends another layer's
#: (models/phi4flash.py: the cross-decoder reads the one full layer's
#: pool): its query projection, its walk of the shared pool, the
#: differential combine and norm, its output projection
ATTN_CROSS = "attn_cross"
#: a Gated Memory Unit (models/phi4flash.py): its two products and the
#: gate over the memory an earlier layer left for the same token
GMU = "gmu"
#: the decode programs' scan over layers: what no inner scope claims is
#: the scan's own plumbing (slicing the stacked weights, stacking the
#: per-layer K/V it returns)
LAYER_SCAN = "layer_scan"
# -- device scopes: trainer --------------------------------------------------
LOSS_AND_GRAD = "loss_and_grad"
OPTIMIZER = "optimizer"

#: instructions the compiler writes anew, whose ``op_name`` is its own
#: and carries no name stack: the stem says what they were made from.
#: ``lax.ragged_dot`` becomes a grouped-matmul kernel named
#: ``ragged-dot-*``, and the only ragged_dot here is the experts'
#: (the path of experts.routed_experts that differentiates keeps it)
REWRITTEN = {"ragged-dot": MOE_EXPERTS}

DEVICE_SCOPES = frozenset((EMBED, ATTN, MLP, LN, LM_HEAD_CE, LM_HEAD,
                           KV_POOL, SAMPLE, SSM, SSM_STATE, MLA,
                           MOE_ROUTER, MOE_EXPERTS, ATTN_FULL, ATTN_WINDOW,
                           ATTN_LINEAR, LINEAR_STATE, ATTN_CROSS, GMU,
                           ATTN_INDEX, LAYER_SCAN,
                           LOSS_AND_GRAD, OPTIMIZER))

# -- Pallas kernel names (``pallas_call(name=)`` in ops/*.py) ----------------
FLASH_FWD, FLASH_DQ, FLASH_DKV = "flash_fwd", "flash_dq", "flash_dkv"
FLASH_RES_FWD, FLASH_RES_DQ, FLASH_RES_DKV = (
    "flash_res_fwd", "flash_res_dq", "flash_res_dkv")
FLASH_TRI_FWD, FLASH_TRI_BWD = "flash_tri_fwd", "flash_tri_bwd"
#: a Mamba mixer's recurrence over a program's columns (ops/ssm_scan.py)
SSM_SCAN = "ssm_scan"
#: a decode column's latent attention over the paged latent pool where
#: it lies (ops/mla_paged_decode.py)
MLA_PAGED_DECODE = "mla_paged_decode"
#: ... and the rotary pool's re-lay for it, once a decode step
MLA_ROTARY_LANES = "mla_rotary_lanes"
#: a prefill's expanded causal latent attention, one sequence
#: (ops/mla_flash_prefill.py)
MLA_FLASH_PREFILL = "mla_flash_prefill"
#: the held experts' rows out of the tokens' and back onto them
#: (ops/moe_dispatch.py)
MOE_DISPATCH, MOE_COMBINE = "moe_dispatch", "moe_combine"
#: a decode column's grouped-query attention over the paged K/V pool
#: where it lies (ops/gqa_paged_decode.py)
GQA_PAGED_DECODE = "gqa_paged_decode"
#: the held experts' gate, up, SwiGLU and down on rows that are few a
#: group, every touched expert's weights streamed once
#: (ops/grouped_swiglu.py)
GROUPED_SWIGLU = "grouped_swiglu"
#: a prefill's chunked delta rule, one call a KDA layer: a head's state
#: held in VMEM across its chunks (ops/kda.py)
KDA_CHUNK = "kda_chunk"
#: a decode wave's delta rule, one call a KDA layer: a head's matrix read
#: once and written once where it lies in the stacked state (ops/kda.py)
KDA_DECODE = "kda_decode"
#: a decode column's grouped-query attention over each row's ring where
#: it lies in the window layers' stacked rings (ops/ring_decode.py)
RING_DECODE = "ring_decode"
#: a prefill's banded grouped-query attention, one sequence, one call an
#: attention layer: the band is data, a K/V head a lane slice of the
#: folded rows where they lie (ops/banded_flash.py)
BANDED_FLASH = "banded_flash"
#: a prefill's chunked delta rule with ONE decay a head, one call a
#: Gated DeltaNet layer: `kda_chunk`'s grid and solve, one (C, C) mask a
#: head where it has pairwise decays a channel (ops/kda.py)
DELTA_CHUNK = "delta_chunk"
#: a decode wave's delta rule with ONE decay a head, one call a Gated
#: DeltaNet layer: a row's matrices (no whole lanes) read once and
#: written once where they lie in the stacked state (ops/kda.py)
DELTA_DECODE = "delta_decode"
KERNELS = (FLASH_FWD, FLASH_DQ, FLASH_DKV,
           FLASH_RES_FWD, FLASH_RES_DQ, FLASH_RES_DKV,
           FLASH_TRI_FWD, FLASH_TRI_BWD, SSM_SCAN, MLA_PAGED_DECODE,
           MLA_ROTARY_LANES, MLA_FLASH_PREFILL, MOE_DISPATCH,
           MOE_COMBINE, GQA_PAGED_DECODE, GROUPED_SWIGLU, KDA_CHUNK,
           KDA_DECODE, RING_DECODE, BANDED_FLASH, DELTA_CHUNK,
           DELTA_DECODE)

# -- host phases -------------------------------------------------------------
SPAN_PREFIX = "raytpu."
ENGINE = "engine"            # layer of serve/engine.py's scheduler loop
STEP = "step"                # one loop iteration with work in it
LOOP = "loop"                # a step's own bookkeeping between two phases
#: leaf phases of one ``raytpu.engine.step``; they partition it
ENGINE_PHASES = (
    LOOP, "admit", "kv.reserve", "prefill_dispatch", "prefill_fence",
    "rng_split", "decode_dispatch", "decode_fence", "emit", "hooks",
    "prefill_chunk", "spec_round", "yield")
#: where the host only waits for the device
ENGINE_FENCES = ("decode_fence", "prefill_fence")
SETUP = "setup"              # layer of an engine's build
#: leaf phases of serve/engine.py ``EngineBase.__init__``, in order:
#: the family's configuration; the parameters (``fam.init`` or the
#: checkpoint's load, the mesh commit, the move to the engine's
#: device; a draft model's too); the K/V pool or cache, pager and
#: snapshots; the jitted programs, their ``instrument`` wrappers and
#: what the constructor compiles ahead of the first request
SETUP_PHASES = ("config", "params", "cache", "programs")


def span_name(layer: str, phase: str) -> str:
    """``raytpu.<layer>.<phase>``: a host phase's name in a trace."""
    return f"{SPAN_PREFIX}{layer}.{phase}"


# ---------------------------------------------------------------------------
# compiled text -> {instruction name: {instruction key: innermost scope}}
# ---------------------------------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}\n]*?op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
#: what a transformation wraps a scope's name in: ``transpose(jvp(attn))``
_WRAPPED = re.compile(r"^(?:jvp|transpose|vmap)\((.*)\)$")
#: the scopes that only hold other scopes: time whose innermost scope is
#: one of these belongs to no part of the model (a layer that lost its
#: scope would land here), so a reader counts it with the unscoped
CONTAINER_SCOPES = frozenset((LOSS_AND_GRAD, LAYER_SCAN))
#: one name and key, two scopes, in two signatures of one program
AMBIGUOUS = "ambiguous"

ScopeMap = Dict[str, Dict[str, str]]


def innermost_scope(op_name: str) -> Optional[str]:
    """The last registered scope on an instruction's ``op_name`` path,
    or None: ``jit(step)/loss_and_grad/transpose(jvp(attn))/ln/mul`` is
    ``ln``; a name of `REWRITTEN` is what it was made from."""
    for stem, scope in REWRITTEN.items():
        if op_name.startswith(stem):
            return scope
    for segment in reversed(op_name.split("/")):
        while True:
            m = _WRAPPED.match(segment)
            if m is None:
                break
            segment = m.group(1)
        if segment in DEVICE_SCOPES:
            return segment
    return None


def hlo_module_name(hlo_text: str) -> Optional[str]:
    """``HloModule jit_pool_step, ...`` -> ``jit_pool_step``: the name a
    trace's ``XLA Modules`` line gives the program."""
    m = _MODULE.search(hlo_text)
    return m.group(1) if m else None


def instruction_key(hlo_line: str) -> str:
    """``%fusion.3 = bf16[8,64]{1,0:T(8,128)} fusion(bf16[...] %p), ...``
    -> ``bf16[8,64]{1,0:T(8,128)} fusion``: an instruction's result type
    (layout included) and opcode.  The compiled text and a trace's op
    event print these alike (the event adds the operands' types), and an
    instruction's name alone is not an identity: another signature of
    the program (a prefill bucket) numbers its ``fusion.N`` anew."""
    return _key_of_body(hlo_line.split(" = ", 1)[-1])


def _key_of_body(body: str) -> str:
    """`instruction_key` of what follows an instruction's ``name = ``
    (the rest may hold `` = `` again, inside a ``backend_config``)."""
    depth = 0
    for i, ch in enumerate(body):
        if ch in "({":
            depth += 1
        elif ch in ")}":
            depth -= 1
        elif ch == " " and depth == 0:      # the type ends here
            j = body.find("(", i)
            return body[:j] if j > 0 else body
    return body


def scope_map_from_hlo(hlo_text: str) -> ScopeMap:
    """``{instruction name: {instruction key: innermost registered
    scope}}`` over every instruction of a compiled program's text whose
    metadata names a scope.  Instructions without one are left out, so
    the map stays small and a reader counts what it does not find as
    unscoped."""
    out: ScopeMap = {}
    for name, body in _INSTRUCTION.findall(hlo_text):
        m = _OP_NAME.search(body)
        scope = innermost_scope(m.group(1)) if m else None
        if scope is not None:
            out[name] = {_key_of_body(body): scope}
    return out


def merge_scope_maps(into: ScopeMap, other: ScopeMap) -> ScopeMap:
    """Add another signature's map of the same program to `into`.  A
    name may stand for different instructions in two signatures; the key
    tells them apart.  Where name and key agree and the scope does not,
    the entry becomes :data:`AMBIGUOUS` and a reader counts it as
    unscoped."""
    for name, keyed in other.items():
        mine = into.setdefault(name, {})
        for key, scope in keyed.items():
            mine[key] = scope if mine.get(key, scope) == scope \
                else AMBIGUOUS
    return into
