"""Multi-head causal attention: dispatcher + XLA reference.

On TPU the hot path is the pallas flash kernel
(ray_tpu/ops/flash_attention.py) — O(T) memory, blocks sized to VMEM, MXU
matmuls.  On CPU (tests, fake meshes) and for short sequences the plain
XLA softmax attention is used; XLA already fuses it well and it doubles
as the numerics oracle for the kernel tests.

Under an active mesh that splits the batch or heads axis the kernel runs
inside ``jax.shard_map`` (GSPMD cannot partition a Mosaic call); it never
falls back to the XLA reference because a mesh is present.

The reference framework has no attention op of its own (it orchestrates
torch modules); this layer exists because on TPU the framework owns the
compute path.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ray_tpu.parallel.mesh import active_mesh
from ray_tpu.parallel.sharding import (DEFAULT_RULES, LogicalAxisRules,
                                       mesh_axes_for_shape)

# measured crossover on v5e (fwd+bwd, head_dim 64): with whole-T forward
# tiles and 256x1024 backward tiles the pallas kernel beats XLA's fused
# attention from T=1024 (12.9ms vs 123ms standalone at B=32, H=12).
_FLASH_MIN_SEQ = 1024


def reference_attention(q, k, v, *, causal: bool = True,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """(B, T, H, D) q/k/v → (B, T, H, D).  Softmax in float32."""
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        T, S = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((T, S), dtype=bool), k=S - T)
        scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def flash_auto_dispatch(T: int, D: int) -> bool:
    """The use_flash=None auto rule, shared with callers that must
    predict the dispatch (e.g. gpt2's mlp_only remat guard, whose memory
    claim only holds when flash actually runs).  A backend that fails to
    initialise raises here; it is never read as "not on TPU"."""
    return jax.default_backend() == "tpu" and T >= _FLASH_MIN_SEQ \
        and T % 128 == 0 and D % 64 == 0


def prefill_attention(q, k, v, *, start: Optional[jnp.ndarray] = None,
                      use_flash: Optional[bool] = None,
                      scale: Optional[float] = None,
                      resident: str = "auto",
                      rules: LogicalAxisRules = DEFAULT_RULES
                      ) -> jnp.ndarray:
    """Prompt-phase attention for the decode path: the whole prompt in
    ONE dispatch instead of a per-token scan.

    start=None is the equal-length fast path — exactly causal_attention,
    so the pallas flash kernel applies under the same dispatch rules as
    training.  start (B,) int32 marks each row's left-pad offset for
    ragged batches: key slots < start[b] are masked out ON TOP of
    causality so pad K/V never contribute to a real token's output.
    The ragged path runs the XLA reference (the flash kernel is
    causal-only); fully-masked pad query rows softmax to uniform —
    finite garbage that the decode masks keep unread.
    """
    if start is None:
        return causal_attention(q, k, v, use_flash=use_flash,
                                scale=scale, resident=resident,
                                rules=rules)
    D = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    T = q.shape[1]
    idx = jnp.arange(T)
    causal = idx[:, None] >= idx[None, :]                 # (Tq, Tk)
    valid = idx[None, :] >= start[:, None]                # (B, Tk)
    mask = causal[None, None, :, :] & valid[:, None, None, :]
    scores = jnp.where(mask, scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def causal_attention(q, k, v, *, use_flash: Optional[bool] = None,
                     scale: Optional[float] = None,
                     resident: str = "auto",
                     rules: LogicalAxisRules = DEFAULT_RULES
                     ) -> jnp.ndarray:
    """Causal MHA on (B, T, H, D) tensors.

    use_flash: True = pallas kernel, False = XLA reference, None = auto
    (pallas on TPU when T >= _FLASH_MIN_SEQ and block-divisible).
    resident: "auto" | "on" | "off" — per-config resident-kv selection
    for the flash kernel (RAYTPU_FLASH_RESIDENT env var still wins as a
    process-wide override; see flash_attention.resolve_resident_mode).
    Ignored on the XLA reference path.
    rules: the logical-axis table the caller shards q/k/v under; with a
    mesh active (``jax.set_mesh``) it names the mesh axes the kernel's
    ``shard_map`` splits the batch and heads dims over.
    """
    T, D = q.shape[1], q.shape[-1]
    if use_flash is None:
        use_flash = flash_auto_dispatch(T, D)
    if not use_flash:
        return reference_attention(q, k, v, causal=True, scale=scale)
    from ray_tpu.ops.flash_attention import (flash_attention,
                                             resolve_resident_mode)
    kernel = functools.partial(
        flash_attention, causal=True, scale=scale,
        resident_kv=resolve_resident_mode(resident))
    mesh = active_mesh()
    if mesh is not None:
        # Attention is independent per (batch, head), so each device
        # runs the kernel on its own block.  The seq dim stays whole
        # (context parallelism is ring_attention's job).  check_vma is
        # off because the kernel's out_shape names no varying axes.
        spec = mesh_axes_for_shape(
            q.shape, ("batch", None, "heads", None), mesh, rules)
        if any(ax is not None for ax in spec):
            kernel = jax.shard_map(kernel, mesh=mesh,
                                   in_specs=(spec, spec, spec),
                                   out_specs=spec, check_vma=False)
    return kernel(q, k, v)
