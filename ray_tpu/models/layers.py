"""What several families' layers are made of: norms, the embedding and
the untied head, the gated MLP, rotary pairs and YaRN's frequencies, the
cross-entropy of a forward's logits, and the constants a seeded
delta-rule family is drawn by.

Nothing here names a family: `cfg` is any config that carries the
fields a function reads (``dtype``, ``rms_eps``, ``vocab_size`` ...;
`yarn_inv_freq`: ``qk_rope_dim``, ``rope_theta``, ``rope_factor``,
``rope_orig_max``, ``beta_fast``, ``beta_slow``).  A family's file
imports what it uses from here and from no other family's file
(tests/test_engine_seam.py).  A helper only ONE family calls stays in
that family's file, same name or not (``jamba.embed``,
``phi4flash.embed``): folding two that differ in an op is a change to
a program's text, not a move.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu._private import scopes

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.LN)
def layernorm(x, scale, bias, eps=1e-5):
    # LN in float32 for stability, cast back to compute dtype.
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def plain_rmsnorm(x, scale, eps):
    """RMSNorm in float32, back in `x`'s dtype, under NO scope of its
    own: a mixer calls it inside its scope (`rmsnorm` is a block's)."""
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1,
                                keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


@jax.named_scope(scopes.LN)
def rmsnorm(x, scale, eps):
    return plain_rmsnorm(x, scale, eps)


def unit(x, eps: float = 1e-6):
    """x (..., hd) float32 over its L2 norm."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


# ---------------------------------------------------------------------------
# embedding, head, gated MLP
# ---------------------------------------------------------------------------

@jax.named_scope(scopes.EMBED)
def embed(params, tokens, cfg):
    return params["wte"].astype(cfg.dtype)[tokens]


@jax.named_scope(scopes.LM_HEAD)
def lm_logits(x, params, cfg):
    """Float32 logits of ``RMSNorm(x)`` through the untied head."""
    x = plain_rmsnorm(x, params["ln_f"]["scale"], cfg.rms_eps)
    return jnp.einsum("...d,vd->...v", x.astype(cfg.dtype),
                      params["head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


@jax.named_scope(scopes.MLP)
def swiglu(x, p, cfg):
    xc = x.astype(cfg.dtype)
    gate = xc @ p["w_gate"].astype(cfg.dtype)
    up = xc @ p["w_up"].astype(cfg.dtype)
    return ((jax.nn.silu(gate) * up)
            @ p["w_down"].astype(cfg.dtype)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary pairs, YaRN
# ---------------------------------------------------------------------------

def yarn_correction_range(cfg) -> Tuple[int, int]:
    """DeepSeek-V3's ``yarn_find_correction_range``: the rotary pairs
    between which the frequencies pass from kept to divided by
    ``rope_factor``; [8, 20] for the published numbers."""
    dim = cfg.qk_rope_dim

    def pair_of(rotations):
        return dim * math.log(cfg.rope_orig_max
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))

    low = max(math.floor(pair_of(cfg.beta_fast)), 0)
    high = min(math.ceil(pair_of(cfg.beta_slow)), dim - 1)
    return low, high


def yarn_inv_freq(cfg) -> np.ndarray:
    """(qk_rope_dim / 2,) float32 inverse frequencies."""
    dim = cfg.qk_rope_dim
    f = cfg.rope_theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return f.astype(np.float32)
    low, high = yarn_correction_range(cfg)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return (f / cfg.rope_factor * (1.0 - keep) + f * keep
            ).astype(np.float32)


def rotate(x, cos, sin):
    """x (..., qk_rope_dim) with cos, sin broadcastable to (...,
    qk_rope_dim / 2): pairs (x_2i, x_2i+1) rotate, in float32."""
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# cross-entropy
# ---------------------------------------------------------------------------

#: The three lm-head + cross-entropy implementations (a config's ``ce_impl``):
#: "dense" materializes f32 (B,T,V) logits; "streaming_xla" is the
#: lax.scan vocab-tile path (ops/vocab_ce.py); "pallas" is the fused
#: MXU-streamed kernel (ops/fused_ce.py) — no (B,T,V) buffer in either
#: pass.  Which wins on the chip is ROADMAP.md A2 (measure once, keep one).
CE_IMPLS = ("dense", "streaming_xla", "pallas")
FLASH_RESIDENT_MODES = ("auto", "on", "off")


def ce_config_problems(ce_impl: str, flash_resident: str, *,
                       loss_chunks: int = 1,
                       seq_parallel: bool = False) -> list:
    """Validation of a config that carries these knobs: returns a list of
    human-readable problems with the CE/attention knob combination (empty
    when valid).  Callers join the list into ONE coherent ValueError so
    an invalid config reports every conflict at once instead of the
    first scattered check to trip."""
    problems = []
    if ce_impl not in CE_IMPLS:
        problems.append(f"ce_impl must be one of {CE_IMPLS} "
                        f"(got {ce_impl!r})")
    else:
        if ce_impl != "dense" and loss_chunks > 1:
            problems.append(
                f"loss_chunks={loss_chunks} requires ce_impl='dense' "
                f"(both bound the logits footprint; pick one)")
        if ce_impl != "dense" and seq_parallel:
            problems.append(
                f"ce_impl={ce_impl!r} needs an unsharded seq axis (the "
                f"(B,T)->(B*T) flatten would reshard under seq "
                f"parallelism)")
    if flash_resident not in FLASH_RESIDENT_MODES:
        problems.append(f"flash_resident must be one of "
                        f"{FLASH_RESIDENT_MODES} (got {flash_resident!r})")
    return problems


@jax.named_scope(scopes.LM_HEAD_CE)
def nll_from_logits(logits, targets, vocab_size: int,
                    padded_vocab: int):
    """Per-token negative log likelihood with the padded-vocab tail masked.

    Gather-free formulation: ``nll = logsumexp(logits) - logits[target]``
    with the target pick as a masked reduction over an iota comparison.
    A ``take_along_axis`` gather along a TENSOR-SHARDED vocab axis makes
    the SPMD partitioner replicate the full (B,T,V) float32 logits; the
    where/iota form partitions cleanly (local reduce + cross-shard sum),
    and XLA fuses the comparison into the reduction so nothing V-sized
    materializes beyond the logits themselves."""
    vocab_iota = lax.broadcasted_iota(jnp.int32, logits.shape,
                                      logits.ndim - 1)
    if padded_vocab != vocab_size:
        logits = jnp.where(vocab_iota < vocab_size, logits,
                           jnp.asarray(-1e9, logits.dtype))
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    target_logit = jnp.sum(
        jnp.where(vocab_iota == targets[..., None], logits, 0),
        axis=-1)
    return lse - target_logit


@jax.named_scope(scopes.LM_HEAD_CE)
def lm_head_nll(hidden, w_vocab_major, targets, cfg) -> jnp.ndarray:
    """Per-token nll via the non-dense CE impls, for a tied or
    an untied head.  hidden (B, T, D); w_vocab_major (V, D) — tied wte, or a
    transposed lm_head for untied models; targets (B, T) int32.  cfg is
    any config carrying ce_impl / vocab_size / vocab_tile / ce_block_n /
    ce_block_v / dtype / padded_vocab.  Returns (B, T) float32."""
    B, T = targets.shape
    h2 = hidden.reshape(B * T, -1)
    t1 = targets.reshape(-1).astype(jnp.int32)
    if cfg.ce_impl == "pallas":
        from ray_tpu.ops.fused_ce import fused_lm_ce
        from ray_tpu.parallel.mesh import active_mesh

        mesh = active_mesh()
        if mesh is not None and mesh.size > 1:
            # GSPMD cannot partition a Mosaic kernel, and the fused CE
            # has no shard_map form (its vocab stream would have to
            # cross the tensor axis): refuse rather than degrade.
            raise NotImplementedError(
                f"ce_impl='pallas' runs on one device; under a "
                f"{mesh.size}-device mesh use ce_impl='dense' or "
                f"'streaming_xla'")
        nll = fused_lm_ce(h2, w_vocab_major, t1, cfg.vocab_size,
                          block_n=cfg.ce_block_n,
                          block_v=min(cfg.ce_block_v, cfg.padded_vocab),
                          compute_dtype=cfg.dtype)
    else:
        from ray_tpu.ops.vocab_ce import streaming_ce

        nll = streaming_ce(h2, w_vocab_major, t1, cfg.vocab_size,
                           min(cfg.vocab_tile, cfg.padded_vocab),
                           cfg.dtype)
    return nll.reshape(B, T)


# ---------------------------------------------------------------------------
# what a seeded delta-rule family is drawn by
# ---------------------------------------------------------------------------

#: the per-token decay a seeded KDA channel is drawn to: exp(g) spans
#: about this range (the family's init)
DECAY_SPAN = (0.9, 0.999)
#: the deviation at which a seeded layer's q~, k~ and v enter their SiLU,
#: on its linear part: the taps are N(0, SILU_IN / (0.02 sqrt(d_model
#: d_conv))), 0.049 at the published width.  At unit scale (taps N(0,
#: 0.5) there) a SiLU's output has a mean of 0.3 of its deviation,
#: every head's read-out carries that mean, the per-head RMSNorm makes
#: it a token-independent vector of the stream larger than the
#: embedding (a quarter of the mixer's output), and every later router
#: scores it: the fullest held expert took 5-8 times the mean of a
#: prefill and a decode wave touched 0.46-0.49 of the held experts
#: where an even load touches 0.56, by the seed (PERF.md section 6,
#: PR 49)
SILU_IN = 0.125
#: a seeded embedding row's deviation, five times a projection's: the
#: first layer's router reads the token over what its softmax layer
#: adds, which without positions is a mean over the context that every
#: later token of a sequence shares
EMBED_STD = 0.1
