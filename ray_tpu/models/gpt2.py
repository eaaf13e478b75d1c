"""GPT-2 family, TPU-first.

The flagship model for the north-star benchmark (BASELINE.json: "GPT-2-125M
language modeling, pjit FSDP across pod").  The reference has no model zoo
of its own — Ray Train wraps user torch modules (reference
python/ray/train/torch/train_loop_utils.py:28 prepare_model); here the
framework ships the model because the TPU path *is* the framework's value.

Design choices (all TPU-motivated, none ported):
  * pure functional init/apply over a param pytree — jit/grad/shard friendly;
  * layers stacked on a leading axis and iterated with `lax.scan` — one
    layer gets traced/compiled once regardless of depth;
  * every param dim carries a logical axis name; DP/FSDP/TP/SP are rule
    tables (ray_tpu/parallel/sharding.py), not model edits;
  * compute in bfloat16 on the MXU, params + optimizer state in float32;
  * per-layer `jax.checkpoint` (remat) so activation memory is O(sqrt)
    and HBM goes to batch instead;
  * attention dispatches to the pallas flash kernel on TPU
    (ray_tpu/ops/flash_attention.py), plain XLA softmax elsewhere.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu._private import scopes
from ray_tpu.models.layers import (ce_config_problems, layernorm,
                                   lm_head_nll, nll_from_logits)
from ray_tpu.parallel.sharding import DEFAULT_RULES, with_logical_constraint


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: int = 3072
    dtype: Any = jnp.bfloat16        # activation/compute dtype (MXU-native)
    param_dtype: Any = jnp.float32   # master weights
    remat: bool = True
    #: "full" = recompute everything (min memory); "dots" = save every
    #: matmul output (incl. the O(T^2) attention scores — usually a bad
    #: trade); "dots_nb" = save matmul outputs with no batch dims, i.e.
    #: the weight matmuls but NOT attention scores — recompute the
    #: HBM-heavy softmax, keep the MXU work.
    remat_policy: str = "full"
    use_flash: Optional[bool] = None  # None = auto (flash on TPU)
    #: Split the (B,T,V) logits/loss computation into this many sequence
    #: chunks so the float32 logits tensor never fully materializes (its
    #: HBM footprint, B*T*V*4 bytes, otherwise dominates and caps batch).
    #: Each chunk is rematerialized in the backward pass.  Leave at 1 when
    #: the sequence axis is mesh-sharded (reshape would break the layout).
    loss_chunks: int = 1
    #: lax.scan unroll factor for the layer stack: >1 lets XLA overlap one
    #: layer's weight loads with the previous layer's compute.
    scan_unroll: int = 1
    #: lm-head + cross-entropy implementation — see CE_IMPLS above.  The
    #: non-dense impls need an unsharded seq axis (the (B,T)->(B*T)
    #: flatten would reshard) and are mutually exclusive with
    #: loss_chunks>1; validated coherently in __post_init__.
    ce_impl: str = "dense"
    #: DEPRECATED alias for ce_impl="streaming_xla" (the pre-round-6
    #: knob); normalized into ce_impl by __post_init__.
    use_streaming_ce: bool = False
    vocab_tile: int = 8192
    #: pallas fused-CE tile sizes (ce_impl="pallas"): block_n rows of
    #: flattened (B*T, D) hidden per vocab stream, block_v vocab columns
    #: per MXU tile.  Defaults sized for GPT-2 D=768 on v5e VMEM
    #: (ops/fused_ce.py).
    ce_block_n: int = 256
    ce_block_v: int = 1024
    #: flash attention kernel family: "auto" = the measured policy of
    #: ops/flash_attention.flash_attention (triangle kernels at causal
    #: T <= 2048, resident-kv past it), "on" forces the resident-kv
    #: kernels, "off" the classic grid kernels.
    #: RAYTPU_FLASH_RESIDENT=1/0 in the env overrides the config — the
    #: process-wide A/B workflow keeps working.
    flash_resident: str = "auto"
    seq_parallel: bool = False  # context parallelism over the "seq" axis
    #: context-parallel algorithm: "ring" (kv blocks rotate by ppermute,
    #: O(T/n) memory) or "ulysses" (head-scatter/seq-gather all-to-all —
    #: cheaper collectives when heads >> seq shards)
    sp_mode: str = "ring"
    #: >0 replaces every block's dense MLP with a mixture-of-experts FF
    #: (ray_tpu.models.moe) routed top-k over the `expert` mesh axis.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    #: weight of the Switch load-balancing aux loss added by gpt2_loss
    moe_aux_weight: float = 0.01
    # pad vocab to a multiple of 128 so the logits matmul tiles the MXU
    # cleanly and the vocab dim shards evenly under tensor parallelism
    vocab_pad_to: int = 128

    def __post_init__(self):
        if self.use_streaming_ce and self.ce_impl == "dense":
            object.__setattr__(self, "ce_impl", "streaming_xla")
        problems = ce_config_problems(
            self.ce_impl, self.flash_resident,
            loss_chunks=self.loss_chunks, seq_parallel=self.seq_parallel)
        if self.use_streaming_ce and self.ce_impl == "pallas":
            problems.append(
                "use_streaming_ce is a deprecated alias for "
                "ce_impl='streaming_xla' and conflicts with "
                "ce_impl='pallas'")
        if problems:
            raise ValueError("invalid GPT2Config: " + "; ".join(problems))

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        p = self.vocab_pad_to
        return (self.vocab_size + p - 1) // p * p


_PRESETS = {
    # name: (n_layer, n_head, d_model)
    "nano": (2, 2, 64),          # test-sized
    "tiny": (4, 4, 128),
    "gpt2": (12, 12, 768),       # 124M — the north-star config
    "gpt2-medium": (24, 16, 1024),
    "gpt2-large": (36, 20, 1280),
    "gpt2-xl": (48, 25, 1600),
}


def gpt2_config(name: str = "gpt2", **overrides) -> GPT2Config:
    n_layer, n_head, d_model = _PRESETS[name]
    kw: Dict[str, Any] = dict(n_layer=n_layer, n_head=n_head,
                              d_model=d_model, d_ff=4 * d_model)
    if name in ("nano", "tiny"):
        kw.update(vocab_size=512, max_seq=128)
    kw.update(overrides)
    return GPT2Config(**kw)


def gpt2_param_count(cfg: GPT2Config) -> int:
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layer
    if cfg.n_experts:
        E = cfg.n_experts
        ff = d * E + E * (2 * d * f + d + f)  # gate + E experts
    else:
        ff = 2 * d * f + d + f
    per_layer = (4 * d * d + 4 * d) + ff + 4 * d  # attn+ff+2ln
    return cfg.vocab_size * d + cfg.max_seq * d + L * per_layer + 2 * d


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def gpt2_logical_axes(cfg: GPT2Config) -> Dict[str, Any]:
    """Pytree (matching gpt2_init's) of logical-axis tuples.

    Leading `None` on block leaves is the stacked-layer axis.  "embed" maps
    to fsdp (ZeRO-3), "heads"/"mlp"/"vocab" to tensor — see
    parallel/sharding.py DEFAULT_RULES.
    """
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "ln_f": {"scale": ("embed",), "bias": ("embed",)},
        "blocks": {
            "ln1": {"scale": (None, "embed"), "bias": (None, "embed")},
            "ln2": {"scale": (None, "embed"), "bias": (None, "embed")},
            "attn": {
                "qkv_w": (None, "embed", None, "heads", "head_dim"),
                "qkv_b": (None, None, "heads", "head_dim"),
                "o_w": (None, "heads", "head_dim", "embed"),
                "o_b": (None, "embed"),
            },
            **({"moe": {
                "gate": (None, "embed", None),
                "w1": (None, "expert", "embed", "mlp"),
                "b1": (None, "expert", "mlp"),
                "w2": (None, "expert", "mlp", "embed"),
                "b2": (None, "expert", "embed"),
            }} if cfg.n_experts else {"mlp": {
                "fc_w": (None, "embed", "mlp"),
                "fc_b": (None, "mlp"),
                "proj_w": (None, "mlp", "embed"),
                "proj_b": (None, "embed"),
            }}),
        },
    }


def gpt2_init(key, cfg: GPT2Config) -> Dict[str, Any]:
    """Initialize parameters (GPT-2 style: N(0, 0.02), residual projections
    scaled by 1/sqrt(2*n_layer))."""
    L, d, f, h, hd = (cfg.n_layer, cfg.d_model, cfg.d_ff, cfg.n_head,
                      cfg.head_dim)
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 8))
    std = 0.02
    res_std = std / math.sqrt(2 * L)

    def norm(kk, shape, s=std):
        return (jax.random.normal(kk, shape, dtype=jnp.float32) * s).astype(pd)

    return {
        "wte": norm(next(k), (cfg.padded_vocab, d)),
        "wpe": norm(next(k), (cfg.max_seq, d), s=0.01),
        "ln_f": {"scale": jnp.ones((d,), pd), "bias": jnp.zeros((d,), pd)},
        "blocks": {
            "ln1": {"scale": jnp.ones((L, d), pd),
                    "bias": jnp.zeros((L, d), pd)},
            "ln2": {"scale": jnp.ones((L, d), pd),
                    "bias": jnp.zeros((L, d), pd)},
            "attn": {
                "qkv_w": norm(next(k), (L, d, 3, h, hd)),
                "qkv_b": jnp.zeros((L, 3, h, hd), pd),
                "o_w": norm(next(k), (L, h, hd, d), s=res_std),
                "o_b": jnp.zeros((L, d), pd),
            },
            **({"moe": {
                "gate": norm(next(k), (L, d, cfg.n_experts)),
                "w1": norm(next(k), (L, cfg.n_experts, d, f)),
                "b1": jnp.zeros((L, cfg.n_experts, f), pd),
                "w2": norm(next(k), (L, cfg.n_experts, f, d),
                           s=res_std),
                "b2": jnp.zeros((L, cfg.n_experts, d), pd),
            }} if cfg.n_experts else {"mlp": {
                "fc_w": norm(next(k), (L, d, f)),
                "fc_b": jnp.zeros((L, f), pd),
                "proj_w": norm(next(k), (L, f, d), s=res_std),
                "proj_b": jnp.zeros((L, d), pd),
            }}),
        },
    }


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _heads_axis_sharded(rules) -> bool:
    """True when the active mesh shards the "heads" logical axis (tensor
    parallelism), in which case the flattened qkv GEMM must be avoided:
    merging (3, h, hd) puts the sharded h behind the unsharded 3, a
    reshape GSPMD cannot represent, forcing a per-layer weight
    all-gather."""
    try:
        from ray_tpu.parallel.mesh import active_mesh
        mesh = active_mesh()
        if mesh is None:
            return False
        from ray_tpu.parallel.sharding import logical_to_mesh_axes
        ax = logical_to_mesh_axes(("heads",), rules)[0]
        if ax is None:
            return False
        size = 1
        for a in (ax if isinstance(ax, (tuple, list)) else (ax,)):
            size *= mesh.shape.get(a, 1)
        return size > 1
    except Exception:  # noqa: BLE001 - no mesh machinery available
        return False


@jax.named_scope(scopes.ATTN)
def _attention(x, p, cfg: GPT2Config, rules):
    B, T, d = x.shape
    h, hd = cfg.n_head, cfg.head_dim
    if _heads_axis_sharded(rules):
        # Megatron-TP path: keep the 5-D einsum so the heads axis stays
        # column-sharded through the contraction.
        qkv = jnp.einsum("btd,dchk->btchk", x, p["qkv_w"].astype(cfg.dtype))
    else:
        # Flattened-matmul form: XLA lowers the 5-D einsum
        # btd,dchk->btchk through a slow transpose path on TPU (measured
        # 10x slower than the equivalent (d, 3*h*hd) matmul on v5e), so
        # collapse the output axes and let the MXU see one big GEMM.
        # The reshape is free: (3, h, hd) are contiguous trailing axes.
        w = p["qkv_w"].astype(cfg.dtype).reshape(d, 3 * h * hd)
        qkv = (x @ w).reshape(B, T, 3, h, hd)
    qkv = qkv + p["qkv_b"].astype(cfg.dtype)
    q, kk, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # (B,T,H,hd)
    q = with_logical_constraint(q, ("batch", "seq", "heads", "head_dim"),
                                rules)
    o = None
    if cfg.seq_parallel:
        o = _ring_attention_sharded(q, kk, v, rules, cfg.sp_mode)
    if o is None:
        from ray_tpu.ops.attention import causal_attention
        o = causal_attention(q, kk, v, use_flash=cfg.use_flash,
                             resident=cfg.flash_resident, rules=rules)
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "attn_out")
    wo = p["o_w"].astype(cfg.dtype).reshape(h * hd, d)
    out = o.reshape(B, T, h * hd) @ wo
    return out + p["o_b"].astype(cfg.dtype)


def _ring_attention_sharded(q, k, v, rules, sp_mode: str = "ring"):
    """Context parallelism: the model stays GSPMD-partitioned, but
    attention (the one op coupling all sequence positions) drops into an
    explicit shard_map running ring attention over the "seq" mesh axis.
    Returns None when no mesh is active (e.g. single-device eval)."""
    import jax
    from jax.sharding import PartitionSpec

    try:
        from ray_tpu.parallel.mesh import active_mesh
        mesh = active_mesh()
        if mesh is None or mesh.shape.get("seq", 1) == 1:
            return None
    except Exception:  # noqa: BLE001 - no mesh machinery available
        return None
    from ray_tpu.ops.ring_attention import (ring_attention,
                                            ulysses_attention)
    from ray_tpu.parallel.sharding import logical_to_mesh_axes

    spec = logical_to_mesh_axes(("batch", "seq", "heads", "head_dim"),
                                rules)
    import functools

    fn = ulysses_attention if sp_mode == "ulysses" else ring_attention
    return jax.shard_map(
        functools.partial(fn, causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)


@jax.named_scope(scopes.MLP)
def _mlp(x, p, cfg: GPT2Config, rules):
    h = jnp.einsum("btd,df->btf", x, p["fc_w"].astype(cfg.dtype))
    h = jax.nn.gelu(h + p["fc_b"].astype(cfg.dtype))
    h = with_logical_constraint(h, ("batch", "seq", "mlp"), rules)
    out = jnp.einsum("btf,fd->btd", h, p["proj_w"].astype(cfg.dtype))
    return out + p["proj_b"].astype(cfg.dtype)


def _moe_cfg(cfg: GPT2Config):
    from ray_tpu.models.moe import MoEConfig

    return MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                     n_experts=cfg.n_experts, top_k=cfg.moe_top_k,
                     capacity_factor=cfg.moe_capacity_factor,
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype)


def _block(x, layer_params, cfg: GPT2Config, rules):
    """Returns (x, moe_aux_loss) — aux is 0.0 for dense blocks."""
    p = layer_params
    x = x + _attention(
        layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"]), p["attn"], cfg,
        rules)
    xm = layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"])
    if cfg.n_experts:
        from ray_tpu.models.moe import moe_apply

        y, aux = moe_apply(p["moe"], xm, _moe_cfg(cfg), rules)
    else:
        y, aux = _mlp(xm, p["mlp"], cfg, rules), jnp.float32(0.0)
    x = x + y
    x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)
    return x, aux


def _flash_active(cfg: GPT2Config, T: int) -> bool:
    """Whether attention will actually take the flash kernel at seq T —
    the precondition for mlp_only remat's memory claim (the un-rematted
    NON-flash path would save O(T^2) score tensors per layer: ~25 GiB at
    B=32/T=1024/12 layers).  Mirrors causal_attention's dispatch."""
    if cfg.use_flash is False or cfg.seq_parallel:
        return False
    if cfg.use_flash is True:
        return True
    from ray_tpu.ops.attention import flash_auto_dispatch

    return flash_auto_dispatch(T, cfg.head_dim)


def gpt2_hidden(params, tokens, cfg: GPT2Config,
                rules=DEFAULT_RULES, return_aux: bool = False):
    """tokens (B, T) int32 → post-ln_f hidden states (B, T, d_model).
    return_aux=True additionally returns the summed MoE load-balance
    loss (0.0 for dense configs)."""
    B, T = tokens.shape
    # Stage the embedding lookup so GSPMD never faces a combined
    # table-shard → activation-shard transition (it would fall back to
    # "involuntary full rematerialization", b/433785288): replicate the
    # casted table FIRST (one all-gather — the partitioner emits the
    # same all-gather for a sharded-table gather anyway), then the local
    # gather inherits the token sharding (batch, seq) directly.
    with jax.named_scope(scopes.EMBED):
        wte = with_logical_constraint(params["wte"].astype(cfg.dtype),
                                      (None, None), rules)
        x = wte[tokens]
        # wpe slice: shard over seq to match x (T, d) + (B, T, d)
        # broadcast; constraining to its param sharding (embed→fsdp)
        # would force an fsdp→seq reshard of the activation instead.
        pos = with_logical_constraint(
            params["wpe"].astype(cfg.dtype)[:T], ("seq", None), rules)
        x = x + pos
        x = with_logical_constraint(x, ("batch", "seq", "embed"), rules)

    if cfg.remat and cfg.remat_policy == "mlp_only" and cfg.n_experts:
        raise NotImplementedError(
            "remat_policy='mlp_only' is a dense-MLP recipe; MoE blocks "
            "use remat_policy='full' (or 'dots_nb')")
    if cfg.remat and cfg.remat_policy == "mlp_only" \
            and _flash_active(cfg, T):
        # Sublayer-granular remat: the attention half is NOT rematted —
        # the flash kernel's backward recomputes score tiles internally
        # from O(T) residuals (q,k,v,o,lse), so re-running the flash
        # forward in the remat pass would be pure waste (~5.7ms/layer on
        # v5e at B=32) — while the activation-heavy MLP half (4x d_ff
        # hidden) is fully rematted.  Net: full-remat memory profile for
        # the MLP, dots-level speed for attention.
        def attn_half(x, p):
            return x + _attention(
                layernorm(x, p["ln1"]["scale"], p["ln1"]["bias"]),
                p["attn"], cfg, rules)

        @partial(jax.checkpoint,
                 policy=jax.checkpoint_policies.nothing_saveable)
        def mlp_half(x, p):
            return x + _mlp(
                layernorm(x, p["ln2"]["scale"], p["ln2"]["bias"]),
                p["mlp"], cfg, rules)

        def scan_body(carry, layer_params):
            h = attn_half(carry, layer_params)
            h = mlp_half(h, layer_params)
            h = with_logical_constraint(h, ("batch", "seq", "embed"),
                                        rules)
            return h, None

        x, _ = lax.scan(scan_body, x, params["blocks"],
                        unroll=cfg.scan_unroll)
        out = layernorm(x, params["ln_f"]["scale"],
                         params["ln_f"]["bias"])
        return (out, jnp.float32(0.0)) if return_aux else out

    block = partial(_block, cfg=cfg, rules=rules)
    if cfg.remat:
        policy = {
            "dots": jax.checkpoint_policies.dots_saveable,
            "dots_nb":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            # save only the attention outputs (B,T,H,hd bf16 — 64 MiB per
            # GPT-2 layer at B=32): the backward pass then skips the
            # ln1 + qkv-matmul + flash-forward recompute, the costliest
            # part of full remat, at ~1/6 the memory of saving all dots.
            "attn_out":
                jax.checkpoint_policies.save_only_these_names("attn_out"),
        }.get(cfg.remat_policy, jax.checkpoint_policies.nothing_saveable)
        block = jax.checkpoint(block, policy=policy)

    def scan_body(carry, layer_params):
        return block(carry, layer_params)

    x, auxes = lax.scan(scan_body, x, params["blocks"],
                        unroll=cfg.scan_unroll)
    out = layernorm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    return (out, jnp.sum(auxes)) if return_aux else out


@jax.named_scope(scopes.LM_HEAD_CE)
def _tied_logits(hidden, wte, cfg: GPT2Config, rules):
    """Tied-embedding projection — the ONE place defining the contract:
    bf16 operands with float32 accumulation (the MXU runs at bf16 rate
    while the softmax/loss still sees float32 logits; a pure-f32 matmul
    would run at 1/3 MXU rate via multi-pass)."""
    logits = jnp.einsum("btd,vd->btv", hidden, wte.astype(cfg.dtype),
                        preferred_element_type=jnp.float32)
    return with_logical_constraint(logits, ("batch", "seq", "vocab"),
                                   rules)


def gpt2_forward(params, tokens, cfg: GPT2Config,
                 rules=DEFAULT_RULES) -> jnp.ndarray:
    """tokens (B, T) int32 → logits (B, T, padded_vocab) float32."""
    x = gpt2_hidden(params, tokens, cfg, rules)
    return _tied_logits(x, params["wte"], cfg, rules)


def _nll_from_logits(logits, targets, cfg):
    """Config-taking shim over nll_from_logits (gpt2-internal)."""
    return nll_from_logits(logits, targets, cfg.vocab_size,
                           cfg.padded_vocab)


def _chunked_ce(hidden, wte, targets, mask, cfg: GPT2Config):
    """Cross-entropy over sequence chunks: the float32 (B,T,V) logits never
    fully materialize (only (B,T/C,V) per chunk, rematerialized in bwd)."""
    B, T, d = hidden.shape
    C = cfg.loss_chunks
    if T % C:
        raise ValueError(f"loss_chunks={C} must divide T={T}")
    Tc = T // C
    hs = jnp.moveaxis(hidden.reshape(B, C, Tc, d), 1, 0)
    ts = jnp.moveaxis(targets.reshape(B, C, Tc), 1, 0)
    ms = jnp.moveaxis(mask.reshape(B, C, Tc), 1, 0)
    wte_c = wte.astype(cfg.dtype)

    @jax.checkpoint
    def chunk_sums(hc, tc, mc):
        logits = jnp.einsum("btd,vd->btv", hc, wte_c,
                            preferred_element_type=jnp.float32)
        nll = _nll_from_logits(logits, tc, cfg)
        return jnp.sum(nll * mc), jnp.sum(mc)

    def body(carry, xs):
        s, n = chunk_sums(*xs)
        return (carry[0] + s, carry[1] + n), None

    (total, count), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ts, ms))
    return total / jnp.maximum(count, 1.0)


def gpt2_loss(params, batch, cfg: GPT2Config,
              rules=DEFAULT_RULES) -> jnp.ndarray:
    """Next-token cross-entropy.  batch = {"tokens": (B, T+1) int32} or
    {"inputs": (B,T), "targets": (B,T)}; padded-vocab tail masked out."""
    if "tokens" in batch:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    else:
        inputs, targets = batch["inputs"], batch["targets"]
    mask = batch.get("mask")
    hidden, aux = gpt2_hidden(params, inputs, cfg, rules,
                              return_aux=True)
    aux_term = cfg.moe_aux_weight * aux if cfg.n_experts else 0.0
    return _ce(hidden, params["wte"], targets, mask, cfg, rules) + aux_term


@jax.named_scope(scopes.LM_HEAD_CE)
def _ce(hidden, wte, targets, mask, cfg: GPT2Config, rules):
    """Mean next-token cross-entropy of `hidden` under the tied head, by
    the configured implementation."""
    if cfg.ce_impl != "dense":
        # valid combinations were enforced at config construction
        # (__post_init__) — one coherent error, not scattered checks here
        nll = lm_head_nll(hidden, wte, targets, cfg)
        if mask is not None:
            m = mask.astype(jnp.float32)
            return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(nll)
    if cfg.loss_chunks > 1:
        if mask is None:
            mask = jnp.ones(targets.shape, jnp.float32)
        return _chunked_ce(hidden, wte, targets,
                           mask.astype(jnp.float32), cfg)
    logits = _tied_logits(hidden, wte, cfg, rules)
    nll = _nll_from_logits(logits, targets, cfg)
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
